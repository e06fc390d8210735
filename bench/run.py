#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

From the root of a checkout.  The cell is `bench/workloads/<cell>.json`;
it names its configuration (`bench/configs/`), its traffic mix
(`bench/traffic/`), and the mix names the loop that runs the window
(`bench/loops/`).  Each metric BENCHMARK.json lists for the cell is read
by `bench/metrics/<metric>.py`: the end-to-end ones with `--trace 0`, the
per-layer ones with `--trace 1`.

The last stdout line is one JSON object: correct, attempted, failed,
metrics, device (and with --trace 1 breakdown), then `checks`, every
number compared with its limit; the same numbers end stderr.  Without a
TPU, or with fewer chips than the cell asks for, the run prints no result
and exits 3.  `--reduced` is the CPU rehearsal: the program's smoke-test
sizes, no chip wanted.  `--fault` breaks the timed path on purpose, on
the chip or in a rehearsal; such a run has to come out not correct.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

FAULTS = ("control", "stale_state", "half_batch", "flip_snapshot",
          "flip_restore", "crash")


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--reduced", action="store_true",
                    help="CPU rehearsal at the program's smoke sizes")
    ap.add_argument("--fault", choices=FAULTS,
                    help="break the timed path on purpose (the check of "
                         "the check; `control` is the fp8 control)")
    return ap.parse_args(argv)


def readers(entries: dict, kind: str) -> dict:
    """{metric: its reader module} for the cell's metrics of one kind."""
    import harness
    return {m["name"]: harness.load_module("metrics", m["name"])
            for m in entries[kind]}


def kernels_of(mods: dict) -> tuple:
    """The kernels the readers name (`KERNELS` in a reader), whose device
    time and operand shapes the trace reduction keeps."""
    return tuple(sorted({k for mod in mods.values()
                         for k in getattr(mod, "KERNELS", ())}))


def main(argv=None) -> int:
    args = parse(argv)
    try:
        import repro  # noqa: F401
        import harness
    except ImportError as e:
        print(f"bench: the program is not in this checkout ({e})",
              file=sys.stderr)
        return 2
    try:
        entries = harness.benchmark_entries(args.workload,
                                            rehearsal=args.reduced)
        cell = harness.load_json("workloads", args.workload + ".json")
        conf = harness.load_json("configs", cell["config"] + ".json")
        mix = harness.load_json("traffic", cell["traffic"] + ".json")
    except (OSError, KeyError, ValueError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2

    import jax
    devices = jax.devices()
    dev = devices[0]
    if not args.reduced:
        if dev.platform != "tpu":
            print(f"bench: needs a TPU, JAX found {dev.platform!r}",
                  file=sys.stderr)
            return 3
        if len(devices) < cell["chips"]:
            print(f"bench: {args.workload} needs {cell['chips']} chips, JAX "
                  f"found {len(devices)}", file=sys.stderr)
            return 3
    cache = None
    if not args.reduced:                  # one fixed directory per checkout
        from repro.launch.cache import use_compile_cache
        cache = use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    counter = harness.CompileCounter()
    cfg, conf = harness.program_config(conf, reduced=args.reduced)
    if args.reduced:
        cell = dict(cell, **cell.get("reduced", {}))
    print(f"[bench] {args.workload}: {conf['name']} x {cell['traffic']}, "
          f"batch {cell['batch']}x{cell['seq']}, seed {args.seed}, "
          f"{dev.platform} {dev.device_kind} x{len(devices)}, "
          f"compile cache {cache}", flush=True)

    ctx = {"conf": conf, "cfg": cfg, "cell": cell, "mix": mix,
           "seed": args.seed, "seconds": args.seconds, "trace": args.trace,
           "fault": args.fault, "counter": counter, "t_process": T_PROCESS,
           "reduced": args.reduced}
    loop = harness.load_module("loops", mix["loop"])
    try:
        rec = loop.run(ctx)
    except Exception:
        traceback.print_exc()
        return 1
    finally:
        if ctx.get("run_id"):
            harness.unlink_segments(ctx["run_id"])
    return report(args, entries, cell, mix, rec, ctx, devices)


def report(args, entries, cell, mix, rec, ctx, devices) -> int:
    dev = devices[0]
    print(f"[bench] compiles_in_window = {rec['compiles_in_window']}",
          flush=True)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell["chips"] if not args.reduced else len(devices),
              "memory_peak_bytes": rec.get("memory_peak_bytes")}
    breakdown = None
    kind = "per_layer" if args.trace else "end_to_end"
    mods = readers(entries, kind)
    if rec.get("trace_dir"):
        import trace_reduce
        kernels = kernels_of(mods)
        try:
            ev = trace_reduce.load(trace_reduce.find_xplane(rec["trace_dir"]),
                                   kernels)
            rec["trace"] = trace_reduce.reduce(ev, kernels)
        finally:
            shutil.rmtree(rec["trace_dir"], ignore_errors=True)
        tr = rec["trace"]
        if tr.get("devices"):
            device["busy_s"] = tr["busy_s"]
            device["window_s"] = tr["window_s"]
            breakdown = {"device_ops": tr["programs"],
                         "idle_gaps": tr["gaps"]}
    ctx["device_kind"] = dev.device_kind
    metrics = {}
    for m in entries[kind]:
        v = mods[m["name"]].read(rec, ctx)
        if v is not None and math.isfinite(v):
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    limits = dict(cell["limits"])
    if mix.get("count") == "flights":
        rec["checks"]["unpublished_flights"] = rec["failed"]
        limits["unpublished_flights"] = 0
    import correct
    ok, rows = correct.verdict(rec["checks"], limits)
    ok = ok and rec["attempted"] > 0
    print(f"[bench] steps {len(rec['steps'])} in {rec['window_s']:.3f} s, "
          f"attempted {rec['attempted']}, failed {rec['failed']}, "
          f"reference {rec['reference_s']:.1f} s, program losses "
          f"{rec['prog']['loss']}, reference losses {rec['ref_loss']}",
          file=sys.stderr)
    if rec["flights"]:
        import statistics
        steps = {}
        for f in rec["flights"]:
            steps.setdefault(f["step"], []).append(f)
        full = [v for v in steps.values()
                if len(v) == ctx["conf"]["sg_size"]]
        walls = [f.get("wall", 0.0) for f in rec["flights"]]
        print(f"[bench] member flights {len(rec['flights'])}, steps flown "
              f"{len(steps)}, by every member {len(full)}, median member "
              f"wall {statistics.median(walls):.3f} s", file=sys.stderr)
    for r in rec["restores"]:
        print(f"[bench] restore {r}", file=sys.stderr)
    for note in rec["notes"]:
        print(f"[bench] note: {note}", file=sys.stderr)
    for name, val in rec.get("info", {}).items():
        print(f"[info] {name} = {val!r}", file=sys.stderr)
    for name, val, lim in rows:
        print(f"[check] {name} = {val!r} (limit {lim!r})", file=sys.stderr)
    out = {"correct": ok, "attempted": rec["attempted"],
           "failed": rec["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {name: {"value": val, "limit": lim}
                     for name, val, lim in rows}
    sys.stdout.flush()
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
