#!/usr/bin/env python3
"""End-to-end check that training with in-memory snapshots runs on a TPU.

  python3 chip_smoke.py             one chip (the default)
  python3 chip_smoke.py --chips 4   the sharded phase only, on a 2x2 mesh

One chip: `repro.launch.train` trains opt-125m at its published widths
(bf16 weights, fp32 Adam moments, ~1.9 GB of state) under the `reft`
backend with four SG members, batch 8 x 2048, snapshots at steps 1, 3
and 5, a persist at step 3, and a `software` failure injected at step 5
while that step's snapshot is in flight, recovered in memory.  The
script watches the run through `train.main`'s `observe` hook and checks:
device encode on, >= 2 snapshots published, no degraded member, an
in-memory recovery tier, the restored state byte-exact to the state the
trainer held at that step, finite loss, no compilation during the second
snapshot flight, and that no child process holds the chip.

Four chips (`--chips 4`): one dbrx-132b MoE layer at its published widths
on a ("data", "model") = 2 x 2 mesh — the expert-parallel `moe_ffn_ep`
against the GSPMD `moe_ffn_gspmd` (fp32, max |diff| < 1e-5 of the
output's scale), then one snapshot of the layer's bf16 weights placed
with the `repro.dist` FSDP/EP shardings and an in-memory restore of every
device's slice through `RestoreTarget(shardings=, mesh=)`, compared byte
for byte.

The last stdout line is `{"ok": true, "device": {...}}`, printed only when
every check passed.  Without a TPU the script exits 2 and prints no
result.  The phases are functions (`train_phase`, `sharded_phase`) that
the tier-1 tests rehearse on the CPU at reduced size.  All device work
runs under the `__main__` guard: the SMP processes are spawned and
re-import this file.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import threading

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


# ------------------------------------------------------------- helpers
class CompileCounter:
    """Counts XLA compilations (persistent-cache hits included) from the
    event JAX records around every backend compile: `n` in the whole
    process, `saving` in threads other than the main one — the snapshot
    flights' pump threads, whereas the training step compiles on the main
    thread.  JAX keeps its listeners for the life of the process, so
    there is one counter per process (`compile_counter`)."""

    def __init__(self):
        from jax import monitoring
        self.n = self.saving = 0
        self._lock = threading.Lock()
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration_secs, **kwargs):
        if event == COMPILE_EVENT:
            with self._lock:
                self.n += 1
                if threading.current_thread() is not threading.main_thread():
                    self.saving += 1


_COUNTER = []


def compile_counter() -> CompileCounter:
    if not _COUNTER:
        _COUNTER.append(CompileCounter())
    return _COUNTER[0]


def peak_bytes(device):
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def tree_bytes_equal(a, b) -> bool:
    import jax
    import numpy as np
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    if len(la) != len(lb):
        return False
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        if x.dtype != y.dtype or x.shape != y.shape:
            return False
        if not np.array_equal(x.reshape(-1).view(np.uint8),
                              y.reshape(-1).view(np.uint8)):
            return False
    return True


def descendants(pid: int):
    """Pids of every live process below `pid` (from /proc)."""
    parent = {}
    for name in os.listdir("/proc"):
        if not name.isdigit():
            continue
        try:
            with open(f"/proc/{name}/stat") as f:
                fields = f.read().rsplit(")", 1)[1].split()
        except OSError:
            continue
        parent[int(name)] = int(fields[1])
    out, todo = [], [pid]
    while todo:
        p = todo.pop()
        kids = [c for c, pp in parent.items() if pp == p]
        out.extend(kids)
        todo.extend(kids)
    return out


def maps_libtpu(pid: int) -> bool:
    try:
        with open(f"/proc/{pid}/maps") as f:
            return "libtpu" in f.read()
    except OSError:
        return False


def shm_bytes(run_id: str) -> int:
    """Bytes of the SMP segments of one run in /dev/shm."""
    total = 0
    for name in os.listdir("/dev/shm"):
        if name.startswith(f"reft-{run_id}-"):
            total += os.stat(os.path.join("/dev/shm", name)).st_size
    return total


def shm_needed(state_bytes: int, sg: int) -> int:
    """Bytes the SG's SMPs hold in /dev/shm once every buffer is used."""
    from repro.core.smp import NBUF, NodeLayout
    return sg * NBUF * NodeLayout(sg, state_bytes).buf_bytes


# ---------------------------------------------------------- phase: train
def train_phase(*, ckpt_dir: str, arch: str = "opt-125m",
                reduced: bool = False, steps: int = 6, batch: int = 8,
                seq: int = 2048, sg: int = 4, snapshot_every: int = 2,
                ckpt_every: int = 4, inject_step: int = 5) -> dict:
    """Train through `repro.launch.train` with an injected software
    failure; returns what the run showed (no verdict)."""
    import jax
    from repro.launch import train

    counter = compile_counter()
    rec = {"losses": [], "flight_compiles": [], "peak_bytes": None,
           "restore": None}
    host = {}                          # step -> state the trainer held
    published = set()                  # steps every member holds clean
    mark = {"compiles": counter.saving}

    def observe(event, **kw):
        sess = kw["sess"]
        engines = sess.checkpointer.group.engines
        clean = min(e.last_clean_step for e in engines)
        if clean >= 0:
            published.add(clean)
        if event == "step":
            rec["losses"].append(kw["loss"])
            if kw["did"]["snapshot"]:
                host[kw["step"]] = jax.device_get(kw["state"])
                for old in sorted(host)[:-3]:
                    del host[old]
                if len(rec["flight_compiles"]) < 2:
                    sess.wait()        # the flight ends inside this window
                    rec["flight_compiles"].append(
                        counter.saving - mark["compiles"])
                    if rec["peak_bytes"] is None:
                        rec["peak_bytes"] = peak_bytes(jax.devices()[0])
            mark["compiles"] = counter.saving
        elif event == "recovered":
            res = kw["res"]
            ref = host.get(res.step)
            rec["restore"] = {
                "tier": res.tier, "step": res.step,
                "exact": ref is not None
                and tree_bytes_equal(res.state, ref)}
        elif event == "end":
            rec["device_encode"] = all(e.stats["device_encode"]
                                       for e in engines)
            rec["ranged_fetch"] = all(e.stats["ranged_fetch"]
                                      for e in engines)
            rec["snapshots"] = sorted(published)
            rec["degraded"] = list(sess.health()["degraded"])
            rec["shm_bytes"] = shm_bytes(sess.run_id)
            kids = descendants(os.getpid())
            rec["children"] = len(kids)
            rec["children_with_libtpu"] = [p for p in kids
                                           if maps_libtpu(p)]
            rec["self_libtpu"] = maps_libtpu(os.getpid())

    argv = ["--arch", arch, "--steps", str(steps), "--batch", str(batch),
            "--seq", str(seq), "--backend", "reft", "--sg-size", str(sg),
            "--snapshot-every", str(snapshot_every),
            "--ckpt-every", str(ckpt_every), "--ckpt-dir", ckpt_dir,
            "--inject", f"{inject_step}:software"]
    if reduced:
        argv.append("--reduced")
    rec["rc"] = train.main(argv, observe=observe)
    return rec


def train_verdict(rec: dict, *, on_tpu: bool) -> dict:
    """Named checks over `train_phase`'s record (all must hold)."""
    from repro.core.policy import SNAPSHOT_TIERS
    losses = rec["losses"]
    restore = rec["restore"] or {}
    checks = {
        "train.main exit 0": rec["rc"] == 0,
        ">= 2 snapshots published": len(rec.get("snapshots", ())) >= 2,
        "0 degraded members": rec.get("degraded") == [],
        "in-memory recovery tier": restore.get("tier") in SNAPSHOT_TIERS,
        "restored state byte-exact": restore.get("exact") is True,
        "finite loss": bool(losses)
        and all(math.isfinite(x) for x in losses),
        "0 compiles in flight 2": len(rec["flight_compiles"]) == 2
        and rec["flight_compiles"][1] == 0,
        "no child holds the chip": rec.get("children_with_libtpu") == [],
    }
    if on_tpu:
        checks["device_encode on"] = rec.get("device_encode") is True
        checks["this process holds the chip"] = rec.get("self_libtpu")
    return checks


# -------------------------------------------------------- phase: sharded
def sharded_phase(*, ckpt_dir: str, arch: str = "dbrx-132b",
                  reduced: bool = False, batch: int = 4, seq: int = 256,
                  mesh_shape=(2, 2), options=None) -> dict:
    """EP vs GSPMD for one MoE layer, then a sharded snapshot and an
    in-memory restore of every device's slice onto the same shardings."""
    import dataclasses

    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.api import CheckpointSession, CheckpointSpec, RestoreTarget
    from repro.configs import get_config
    from repro.dist.api import use_mesh
    from repro.dist.shardings import named, param_specs
    from repro.models.moe import init_moe, moe_ffn_ep, moe_ffn_gspmd

    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    cfg = dataclasses.replace(cfg, moe_ep=True, fsdp=True)
    axes = ("data", "model")
    mesh = jax.make_mesh(tuple(mesh_shape), axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    rec = {"mesh": dict(zip(axes, mesh_shape)),
           "devices": [str(d) for d in mesh.devices.reshape(-1)]}

    def place(c):
        shapes = jax.eval_shape(lambda: init_moe(jax.random.PRNGKey(0), c))
        specs = param_specs(c, shapes)
        sh = named(specs, shapes, mesh)
        p = jax.jit(lambda: init_moe(jax.random.PRNGKey(0), c),
                    out_shardings=sh)()
        return p, specs, sh

    # (a) expert-parallel vs GSPMD, fp32 at the layer's widths.  Dropless
    # routing (DBRX's own MoE drops no token): with a capacity limit the
    # two paths drop different tokens, since EP sizes capacity per data
    # shard.  Experts are sharded over "model" only: under FSDP the GSPMD
    # reference reduces the data-sharded contraction in another order
    # than EP's all-gathered weights.  Full-precision matmuls, so the two
    # differ only in the order GSPMD's partitioned combine sums in.
    c32 = dataclasses.replace(cfg, dtype="float32", param_dtype="float32",
                              capacity_factor=float(cfg.num_experts),
                              fsdp=False)
    p, _, _ = place(c32)
    x = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(1), (batch, seq, cfg.d_model)),
        NamedSharding(mesh, P("data", None, None)))
    with use_mesh(mesh), jax.default_matmul_precision("highest"):
        y_ep, _, _ = jax.jit(lambda p, x: moe_ffn_ep(p, c32, x))(p, x)
        y_ref, _, _ = jax.jit(lambda p, x: moe_ffn_gspmd(p, c32, x))(p, x)
    rec["ep_max_abs_diff"] = float(np.max(np.abs(
        np.asarray(y_ep) - np.asarray(y_ref))))
    rec["gspmd_max_abs"] = float(np.max(np.abs(np.asarray(y_ref))))
    rec["ep_rel_diff"] = rec["ep_max_abs_diff"] / max(rec["gspmd_max_abs"],
                                                      1.0)
    rec["ep_finite"] = bool(np.isfinite(np.asarray(y_ep)).all())
    del p, x, y_ep, y_ref

    # (b) sharded snapshot + in-memory restore onto the same shardings
    state, specs, shardings = place(cfg)
    rec["state_bytes"] = int(sum(a.nbytes for a in jax.tree.leaves(state)))
    rec["leaf_shardings"] = {k: str(v.spec) for k, v in shardings.items()}
    spec = CheckpointSpec(backend="reft", ckpt_dir=ckpt_dir, sg_size=4,
                          snapshot_every_steps=1,
                          checkpoint_every_steps=10 ** 9,
                          options=dict(options or {}))
    flat_state = jax.tree.leaves(state)
    flat_sh = jax.tree.leaves(shardings)
    with CheckpointSession(spec, state) as sess:
        ok = sess.snapshot(state, 1, wait=True)
        engines = sess.checkpointer.group.engines
        rec["snapshot_ok"] = bool(ok) and all(
            e.last_clean_step == 1 for e in engines)
        rec["device_encode"] = all(e.stats["device_encode"] for e in engines)
        rec["peak_bytes_after_snapshot"] = [peak_bytes(d)
                                            for d in mesh.devices.reshape(-1)]
        # each device's slice comes from the restore of its own mesh
        # coordinate (the loader reads only that rank's byte ranges)
        pieces, tiers = [dict() for _ in flat_state], set()
        for pos in np.ndindex(*mesh_shape):
            dev = mesh.devices[pos]
            res = sess.restore(target=RestoreTarget(
                shardings=specs, mesh=mesh, coord=dict(zip(axes, pos))))
            tiers.add(res.tier)
            for i, (leaf, got) in enumerate(
                    zip(flat_state, jax.tree.leaves(res.state))):
                idx = flat_sh[i].devices_indices_map(leaf.shape)[dev]
                pieces[i][dev] = jax.device_put(np.asarray(got)[idx], dev)
            del res
        restored = [jax.make_array_from_single_device_arrays(
            leaf.shape, sh, [pieces[i][d] for d in sh.addressable_devices])
            for i, (leaf, sh) in enumerate(zip(flat_state, flat_sh))]
        exact = all(
            r.sharding == leaf.sharding and all(
                tree_bytes_equal(a.data, b.data) for a, b in
                zip(r.addressable_shards, leaf.addressable_shards))
            for r, leaf in zip(restored, flat_state))
        rec["restore_tiers"] = sorted(tiers)
        rec["restore_exact"] = bool(exact)
        rec["peak_bytes_end"] = [peak_bytes(d)
                                 for d in mesh.devices.reshape(-1)]
    return rec


def sharded_verdict(rec: dict, *, on_tpu: bool) -> dict:
    from repro.core.policy import SNAPSHOT_TIERS
    # tests/test_moe_ep.py's 1e-5, taken relative to the output's scale:
    # the layer's init puts |y| in the thousands, where fp32 resolves ~1e-4
    checks = {
        "EP equals GSPMD (max |diff| < 1e-5 max |y|)":
        rec["ep_rel_diff"] < 1e-5 and rec["ep_finite"],
        "sharded snapshot published": rec["snapshot_ok"],
        "in-memory restore tier": bool(rec["restore_tiers"])
        and set(rec["restore_tiers"]) <= SNAPSHOT_TIERS,
        "every device's slice byte-exact": rec["restore_exact"],
    }
    if on_tpu:
        checks["device_encode on"] = rec["device_encode"] is True
    return checks


# ---------------------------------------------------------------- main
def _report(title: str, rec: dict, checks: dict) -> bool:
    for k, v in rec.items():
        if k != "losses":
            print(f"[{title}] {k} = {v}")
    for name, ok in checks.items():
        print(f"[{title}] check {'PASS' if ok else 'FAIL'}: {name}")
    return all(checks.values())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    try:
        import repro  # noqa: F401
    except ImportError:
        print(f"chip_smoke: the repro package is not under {SRC}",
              file=sys.stderr)
        return 2

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    from repro.launch.cache import use_compile_cache
    print(f"[smoke] compile cache: {use_compile_cache()}")
    dev = devices[0]
    print(f"[smoke] device {dev.platform} {dev.device_kind} "
          f"x{len(devices)}")
    ckpt_dir = os.path.join(ROOT, ".smoke_ckpt")
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    try:
        if args.chips == 4:
            rec = sharded_phase(ckpt_dir=ckpt_dir)
            ok = _report("sharded", rec, sharded_verdict(rec, on_tpu=True))
        else:
            ok = _run_one_chip(ckpt_dir)
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


def _run_one_chip(ckpt_dir: str) -> bool:
    import jax
    from repro.configs import get_config
    from repro.train.steps import init_train_state

    cfg = get_config("opt-125m")
    shapes = jax.eval_shape(lambda: init_train_state(cfg, 0).tree())
    w = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(shapes))
    need = shm_needed(w, 4)
    st = os.statvfs("/dev/shm")
    free = st.f_bavail * st.f_frsize
    print(f"[train] state W = {w} bytes; SMPs need {need} bytes of "
          f"/dev/shm, {free} free")
    if free < need:
        print(f"chip_smoke: /dev/shm has {free} bytes free, the four SMPs "
              f"need {need}", file=sys.stderr)
        return False
    rec = train_phase(ckpt_dir=ckpt_dir)
    print(f"[train] first loss = {rec['losses'][0]}, last loss = "
          f"{rec['losses'][-1]}")
    return _report("train", rec, train_verdict(rec, on_tpu=True))


if __name__ == "__main__":
    sys.exit(main())
