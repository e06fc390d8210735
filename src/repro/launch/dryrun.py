"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh)
against 512 placeholder CPU devices, and extract the roofline terms.

The os.environ lines below MUST run before ANY other import (jax locks the
device count on first init).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--json out]
"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")

import argparse
import json
import math
import re
import sys
import time

import jax
import jax.numpy as jnp

from repro.configs import (ASSIGNED_ARCHS, INPUT_SHAPES, get_config,
                           shape_supported)
from repro.data.pipeline import input_specs
from repro.dist import shardings as SH
from repro.dist.api import use_mesh
from repro.launch.mesh import (HBM_BW, ICI_BW, PEAK_FLOPS_BF16,
                               make_production_mesh)
from repro.models import model as M
from repro.train.steps import init_train_state, make_train_step

_COLL_RE = re.compile(
    r"(\w+)\[([0-9,]*)\][^=]*\b"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}


def collective_bytes(hlo_text: str) -> dict:
    """Sum result-operand bytes of every collective op in the HLO."""
    out = {}
    for m in _COLL_RE.finditer(hlo_text):
        dt, dims, op = m.group(1), m.group(2), m.group(3)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out[op] = out.get(op, 0) + n * _DTYPE_BYTES[dt]
    out["total"] = sum(v for k, v in out.items() if k != "total")
    return out


def cost_dict(compiled) -> dict:
    """compiled.cost_analysis() as a dict (empty when unavailable)."""
    return compiled.cost_analysis() or {}


def _abstract_state(cfg):
    return jax.eval_shape(lambda: init_train_state(cfg, 0).tree())


def build_lowered(arch: str, shape_name: str, mesh, verbose=False,
                  unroll=False, cfg=None):
    """Returns (lowered, meta) for the (arch, shape) pair on `mesh`.

    unroll=True unrolls the layer scan so cost_analysis counts every layer
    (XLA prices a while body once) — used for the roofline table; the
    scanned variant is used for the (faster) compile-proof runs.
    """
    cfg = cfg or get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    ok, why = shape_supported(cfg, shape)
    if not ok:
        raise SkipPair(why)

    with use_mesh(mesh):
        if shape.kind == "decode":
            params_sh = jax.eval_shape(
                lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
            cache_sh = jax.eval_shape(
                lambda: M.init_cache(cfg, shape.global_batch, shape.seq_len))
            tokens_sh = jax.ShapeDtypeStruct((shape.global_batch, 1),
                                             jnp.int32)
            p_specs = SH.named(SH.param_specs(cfg, params_sh), params_sh,
                               mesh)
            c_specs = SH.named(
                SH.cache_specs(cfg, cache_sh, shape.global_batch, mesh),
                cache_sh, mesh)
            t_spec = SH.named(SH.batch_specs(cfg, {"t": tokens_sh}),
                              {"t": tokens_sh}, mesh)["t"]

            def serve_step(params, cache, tokens):
                return M.decode_step(cfg, params, cache, tokens,
                                     unroll=unroll)

            fn = jax.jit(serve_step,
                         in_shardings=(p_specs, c_specs, t_spec),
                         out_shardings=(None, c_specs))
            lowered = fn.lower(params_sh, cache_sh, tokens_sh)
            tokens_per_step = shape.global_batch
            train = False
        elif shape.kind == "prefill":
            params_sh = jax.eval_shape(
                lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
            batch_sh = input_specs(cfg, shape)
            p_specs = SH.named(SH.param_specs(cfg, params_sh), params_sh,
                               mesh)
            b_specs = SH.named(SH.batch_specs(cfg, batch_sh), batch_sh, mesh)

            def prefill_step(params, batch):
                logits, caches = M.logits_fn(cfg, params, batch,
                                             unroll=unroll)
                return logits, caches

            fn = jax.jit(prefill_step, in_shardings=(p_specs, b_specs))
            lowered = fn.lower(params_sh, batch_sh)
            tokens_per_step = shape.global_batch * shape.seq_len
            train = False
        else:
            state_sh = _abstract_state(cfg)
            batch_sh = input_specs(cfg, shape)
            s_specs = SH.named(SH.state_specs(cfg, state_sh), state_sh, mesh)
            b_specs = SH.named(SH.batch_specs(cfg, batch_sh), batch_sh, mesh)
            step = make_train_step(cfg, unroll=unroll)
            fn = jax.jit(step, in_shardings=(s_specs, b_specs),
                         out_shardings=(s_specs, None))
            lowered = fn.lower(state_sh, batch_sh)
            tokens_per_step = shape.global_batch * shape.seq_len
            train = True
    meta = {"arch": arch, "shape": shape_name, "unroll": unroll,
            "tokens_per_step": tokens_per_step, "train": train,
            "chips": math.prod(mesh.axis_sizes),
            "mesh": "x".join(map(str, mesh.axis_sizes))}
    return lowered, meta


class SkipPair(Exception):
    pass


def analyse(lowered, compiled, meta, cfg) -> dict:
    """Roofline terms.  NOTE: compiled artifacts are the *per-device* SPMD
    program, so cost_analysis flops/bytes and HLO operand shapes are already
    per-chip — terms divide by per-chip peaks, not (chips x peak)."""
    chips = meta["chips"]
    cost = cost_dict(compiled)
    flops = float(cost.get("flops", 0.0))          # per chip
    bytes_acc = float(cost.get("bytes accessed", 0.0))
    coll = collective_bytes(compiled.as_text())    # per chip

    t_compute = flops / PEAK_FLOPS_BF16
    t_memory = bytes_acc / HBM_BW
    t_coll = coll["total"] / ICI_BW
    dominant = max((("compute", t_compute), ("memory", t_memory),
                    ("collective", t_coll)), key=lambda kv: kv[1])[0]

    n_active = cfg.active_param_count()
    mult = 6 if meta["train"] else 2
    model_flops = mult * n_active * meta["tokens_per_step"]   # global

    try:
        mem = compiled.memory_analysis()
        mem_d = {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "peak_bytes": int(mem.peak_memory_in_bytes),
        }
    except Exception:
        mem_d = {}

    return {
        **meta,
        "hlo_flops_per_chip": flops,
        "hlo_flops_global": flops * chips,
        "hlo_bytes_per_chip": bytes_acc,
        "collective_bytes": coll,
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_coll,
        "dominant": dominant,
        "model_flops": model_flops,
        "useful_compute_ratio": (model_flops / (flops * chips))
        if flops else None,
        "params_total": cfg.param_count(),
        "params_active": n_active,
        "memory": mem_d,
    }


def run_pair(arch: str, shape_name: str, *, multi_pod: bool,
             verbose: bool = True, unroll: bool = False, cfg=None) -> dict:
    cfg = cfg or get_config(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    lowered, meta = build_lowered(arch, shape_name, mesh, unroll=unroll,
                                  cfg=cfg)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()
    rec = analyse(lowered, compiled, meta, cfg)
    rec["lower_s"] = round(t1 - t0, 2)
    rec["compile_s"] = round(t2 - t1, 2)
    if verbose:
        mem = rec.get("memory", {})
        print(f"[ok] {arch} x {shape_name} mesh={rec['mesh']} "
              f"flops/chip={rec['hlo_flops_per_chip']:.3e} "
              f"bytes/chip={rec['hlo_bytes_per_chip']:.3e} "
              f"coll/chip={rec['collective_bytes']['total']:.3e} "
              f"dom={rec['dominant']} "
              f"useful={rec['useful_compute_ratio'] and round(rec['useful_compute_ratio'],3)} "
              f"args/chip={mem.get('argument_bytes', 0)/2**30:.2f}GiB "
              f"(lower {rec['lower_s']}s, compile {rec['compile_s']}s)",
              flush=True)
    return rec


def extrapolation_period(cfg) -> int:
    """Smallest layer count that tiles the full model exactly (hybrid
    period x local:global interleave)."""
    period, _ = M._stack_period(cfg)
    if cfg.global_every:
        period = math.lcm(period, cfg.global_every)
    return period


_SCALARS = ("hlo_flops_per_chip", "hlo_bytes_per_chip", "t_compute_s",
            "t_memory_s", "t_collective_s")


def run_pair_roofline(arch: str, shape_name: str, *, multi_pod: bool = False,
                      cfg=None, verbose: bool = True) -> dict:
    """Exact roofline terms via layer extrapolation: compile the unrolled
    program at L=P and L=2P layers (P = pattern period) and extrapolate
    linearly — exact because layers are periodic and XLA cost is additive
    in unrolled layers.  Avoids multi-minute full unrolled compiles."""
    import dataclasses
    cfg = cfg or get_config(arch)
    P_ = extrapolation_period(cfg)
    L = cfg.num_layers
    if L <= 2 * P_:
        rec = run_pair(arch, shape_name, multi_pod=multi_pod, unroll=True,
                       cfg=cfg, verbose=verbose)
        rec["extrapolated"] = False
        return rec
    c1 = dataclasses.replace(cfg, name=cfg.name, num_layers=P_)
    c2 = dataclasses.replace(cfg, name=cfg.name, num_layers=2 * P_)
    r1 = run_pair(arch, shape_name, multi_pod=multi_pod, unroll=True,
                  cfg=c1, verbose=False)
    r2 = run_pair(arch, shape_name, multi_pod=multi_pod, unroll=True,
                  cfg=c2, verbose=False)

    def ex(v1, v2):
        return v1 + (v2 - v1) * (L - P_) / P_

    rec = dict(r2)
    for k in _SCALARS:
        rec[k] = ex(r1[k], r2[k])
    coll = {k: ex(r1["collective_bytes"].get(k, 0),
                  r2["collective_bytes"].get(k, 0))
            for k in set(r1["collective_bytes"]) | set(r2["collective_bytes"])}
    rec["collective_bytes"] = coll
    rec["t_collective_s"] = coll["total"] / ICI_BW
    rec["hlo_flops_global"] = rec["hlo_flops_per_chip"] * rec["chips"]
    rec["dominant"] = max(
        (("compute", rec["t_compute_s"]), ("memory", rec["t_memory_s"]),
         ("collective", rec["t_collective_s"])), key=lambda kv: kv[1])[0]
    rec["params_total"] = cfg.param_count()
    rec["params_active"] = cfg.active_param_count()
    mult = 6 if rec["train"] else 2
    rec["model_flops"] = mult * rec["params_active"] * rec["tokens_per_step"]
    rec["useful_compute_ratio"] = (rec["model_flops"]
                                   / rec["hlo_flops_global"])
    rec["extrapolated"] = True
    rec["memory"] = {}            # memory comes from the full scanned proof
    rec["lower_s"] = r1["lower_s"] + r2["lower_s"]
    rec["compile_s"] = r1["compile_s"] + r2["compile_s"]
    if verbose:
        print(f"[ok] {arch} x {shape_name} mesh={rec['mesh']} (extrap {P_}->"
              f"{L}L) flops/chip={rec['hlo_flops_per_chip']:.3e} "
              f"bytes/chip={rec['hlo_bytes_per_chip']:.3e} "
              f"coll/chip={coll['total']:.3e} dom={rec['dominant']} "
              f"useful={round(rec['useful_compute_ratio'], 3)}", flush=True)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--json", default=None, help="append JSONL records here")
    ap.add_argument("--unroll", action="store_true",
                    help="unroll layer scans for exact roofline FLOPs")
    ap.add_argument("--mode", choices=["proof", "roofline"], default="proof",
                    help="roofline = layer-extrapolated unrolled analysis")
    args = ap.parse_args(argv)

    pairs = []
    if args.all:
        for a in ASSIGNED_ARCHS:
            for s in INPUT_SHAPES:
                pairs.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        pairs = [(args.arch, args.shape)]

    records = []
    failures = 0
    for a, s in pairs:
        try:
            if args.mode == "roofline":
                rec = run_pair_roofline(a, s, multi_pod=args.multi_pod)
            else:
                rec = run_pair(a, s, multi_pod=args.multi_pod,
                               unroll=args.unroll)
            records.append(rec)
        except SkipPair as e:
            print(f"[skip] {a} x {s}: {e}", flush=True)
            records.append({"arch": a, "shape": s, "skipped": str(e)})
        except Exception as e:
            failures += 1
            print(f"[FAIL] {a} x {s}: {type(e).__name__}: {e}", flush=True)
            records.append({"arch": a, "shape": s, "error": repr(e)})
        if args.json:
            with open(args.json, "a") as f:
                f.write(json.dumps(records[-1]) + "\n")
    print(f"done: {len(records)} pairs, {failures} failures")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
