"""End-to-end training driver with pluggable fault tolerance.

Trains a real model (JAX CPU here; the same code path jit-lowers onto the
production mesh) under any registered `Checkpointer` backend — the paper's
REFT stack or a disk baseline — selected by one flag, so overhead and
recovery comparisons are apples-to-apples.  Optional fault injection
exercises the recovery ladder mid-run and verifies training resumes from
the recovered state.

  PYTHONPATH=src python -m repro.launch.train --arch opt-125m --steps 50 \\
      --batch 2 --seq 256 --backend reft --sg-size 4 --snapshot-every 2 \\
      --inject 20:software --inject 35:node

Elastic restart (reshard-on-restore): `--resume` works with a DIFFERENT
`--sg-size` than the run that wrote the checkpoint — the distributed
loader rediscovers the saved layout from the REFT-Ckpt family heads and
ranges its reads accordingly, so an n-node run restores onto m nodes.

A member whose saving sidecar is still degraded when the run ends makes
`main` print that member's first flight error and exit 1: training
rides through a lost sidecar, but the run does not report success
without the protection it was asked for.
"""
from __future__ import annotations

import argparse
import sys
import time
from typing import Callable, Optional

import numpy as np

import jax
import jax.numpy as jnp


def _load_stats_str(ld) -> str:
    """One-line per-phase load decomposition for resume/recover prints."""
    if ld is None:
        return ""
    out = (f" read={ld.bytes_read / 1e6:.1f}MB"
           f" decoded={ld.decoded_bytes / 1e6:.1f}MB"
           f" read_s={ld.read_seconds:.3f}")
    if ld.h2d_seconds:
        out += f" h2d_s={ld.h2d_seconds:.3f}"
    if ld.resharded:
        out += f" resharded={ld.saved_n}->{ld.target_n}"
    return out


def main(argv=None, *, observe: Optional[Callable[..., None]] = None):
    """Train; returns the process exit code.

    `observe(event, **info)`, when given, is called inside the session:
    "step" after each `after_step` (step, state, loss, did, sess),
    "recovered" after each injected failure's restore (res, sess), and
    "end" after the final drain (sess).  `chip_smoke.py` checks the run
    through it."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="opt-125m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--backend", default="reft",
                    choices=["reft", "objstore", "sync_disk", "async_disk",
                             "null"])
    ap.add_argument("--sg-size", type=int, default=4)
    ap.add_argument("--snapshot-every", type=int, default=2)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--ckpt-dir", default="/tmp/reft-train-ckpt")
    ap.add_argument("--resume", action="store_true",
                    help="restore-on-entry from ckpt-dir if possible")
    ap.add_argument("--auto-tune", action="store_true",
                    help="Appendix-A adaptive snapshot cadence")
    ap.add_argument("--blocking-persist", action="store_true",
                    help="run cadence persists inline (the pre-overlap "
                         "behavior) instead of fire-and-poll")
    ap.add_argument("--delta", action="store_true",
                    help="dirty-delta snapshotting: for MoE archs the "
                         "router's touched-expert mask feeds the dirty "
                         "provider; dense archs fall back to the "
                         "per-bucket digest compare")
    ap.add_argument("--inject", action="append", default=[],
                    help="STEP:KIND[:NODE]  (kind: software|node|smp|"
                         "laggard|corrupt-stripe|slow-persist|preempt)")
    ap.add_argument("--graceful-inject", action="store_true",
                    help="drain in-flight saves before each injection "
                         "(default: mid-flight, like a real failure)")
    ap.add_argument("--no-reft", action="store_true",
                    help="legacy alias for --backend null")
    args = ap.parse_args(argv)
    if args.no_reft:
        args.backend = "null"

    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    from repro.api import CheckpointSession, CheckpointSpec
    from repro.core.recovery import RecoveryError
    from repro.configs import get_config
    from repro.configs.base import InputShape
    from repro.data.pipeline import SyntheticDataset
    from repro.train.steps import init_train_state, make_train_step

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    shape = InputShape("cli", args.seq, args.batch, "train")
    from repro.supervise.inject import parse_scenario
    injections = {}
    for item in args.inject:
        try:
            sc = parse_scenario(item, default_node=-1)
        except ValueError as e:
            ap.error(str(e))
        injections[sc.step] = sc
    if injections and args.backend == "null":
        ap.error("--inject needs a backend that can restore (not null)")
    if args.delta and args.backend not in ("reft", "objstore"):
        ap.error("--delta needs the reft backend family")

    print(f"[train] arch={cfg.name} params={cfg.param_count():,} "
          f"batch={args.batch}x{args.seq} backend={args.backend}"
          + (" delta" if args.delta else ""))
    if args.delta and cfg.num_experts:
        # enable BEFORE the step function traces, so the router's
        # touched-expert debug callback is staged into the jaxpr
        from repro.models.moe import TOUCHED
        TOUCHED.enable(cfg.num_experts)
    state = init_train_state(cfg, 0).tree()
    ds = SyntheticDataset(cfg, shape, seed=0)
    # no with_step_boundary wrapper here: sess.after_step runs every step
    # and already ticks the HASC gate (one boundary signal per step)
    step_fn = jax.jit(make_train_step(cfg))

    spec = CheckpointSpec(
        backend=args.backend,
        ckpt_dir=args.ckpt_dir,
        sg_size=args.sg_size,
        snapshot_every_steps=args.snapshot_every,
        checkpoint_every_steps=args.ckpt_every,
        resume=args.resume,
        auto_tune=args.auto_tune,
        options=dict(
            **({"persist_blocking": True} if args.blocking_persist else {}),
            **({"delta": True} if args.delta else {}),
        ),
    )

    losses = []
    t0 = time.time()
    step = int(state["step"])
    with CheckpointSession(spec, state) as sess:
        if args.delta and cfg.num_experts \
                and hasattr(sess.checkpointer, "set_dirty_provider"):
            from repro.core.delta import expert_dirty_ranges
            from repro.models.moe import TOUCHED
            fspec = sess.checkpointer.group.engines[0].spec
            sess.checkpointer.set_dirty_provider(
                lambda: expert_dirty_ranges(
                    fspec, TOUCHED.consume(),
                    held=range(cfg.num_experts_held)))
        if sess.restored is not None:
            res = sess.restored
            print(f"[resume] tier={res.tier} step={res.step}"
                  + _load_stats_str(res.load))
            state = jax.tree.map(jnp.asarray, res.state)
            ds.restore(res.extra_meta)
            step = res.step
        while step < args.steps:
            batch = next(ds)
            state, metrics = step_fn(state, batch)
            step = int(state["step"])
            losses.append(float(metrics["loss"]))
            did = sess.after_step(state, step, extra_meta=ds.state())
            if observe is not None:
                observe("step", step=step, state=state, loss=losses[-1],
                        did=did, sess=sess)

            if step in injections:
                sc = injections.pop(step)
                kind = sc.kind
                node = sc.node if sc.node >= 0 \
                    else (0 if kind == "software" else 1)
                print(f"[inject] {kind} failure at step {step} "
                      f"(node {node}"
                      + ("" if args.graceful_inject else ", mid-flight")
                      + ")")
                sess.inject(kind, node=node,
                            graceful=args.graceful_inject,
                            **sc.merged_params())
                if kind in ("laggard", "slow-persist"):
                    continue           # perf faults: nothing to restore
                if kind == "preempt":
                    # ride out the grace window; health() ticks the
                    # deadline and hard-fails the node when it expires
                    deadline = time.monotonic() + 5.0
                    while node not in sess.health().get("preempted",
                                                        [node]):
                        if time.monotonic() > deadline:
                            ap.error("preempt grace window never expired")
                        # deadline-bounded grace-window poll in the CLI
                        # harness (the sim has no event to wait on)
                        # analyze: ok ANZ007
                        time.sleep(0.05)
                try:
                    res = sess.restore()
                except RecoveryError as e:
                    ap.error(f"injected {kind} failure at step {step} is "
                             f"unrecoverable: {e} (no completed save yet — "
                             f"lower --snapshot-every or inject later)")
                print(f"[recover] tier={res.tier} step={res.step}"
                      + _load_stats_str(res.load))
                if observe is not None:
                    observe("recovered", res=res, sess=sess)
                state = jax.tree.map(jnp.asarray, res.state)
                ds.restore(res.extra_meta)
                step = res.step

            if step % 10 == 0 or step == args.steps:
                print(f"  step {step:5d} loss {losses[-1]:.4f} "
                      f"({(time.time()-t0)/max(step,1):.2f}s/step)",
                      flush=True)
        sess.drain()               # join async persists + collect events
        if observe is not None:
            observe("end", sess=sess)
        health = sess.health()
        st = sess.stats()
        # engine-side timing when the backend exposes it (async launches
        # make the trainer-side snapshot_seconds near-zero by design)
        snaps = st.get("engine_snapshots") or st.get("snapshot", 0)
        secs = st.get("engine_seconds", st.get("snapshot_seconds", 0.0))
        print(f"[{args.backend}] snapshots={snaps} "
              f"persists={st.get('persist', 0)} "
              f"persist_inflight={st.get('persist_inflight', 0)} "
              f"persist_overlap_s={st.get('persist_overlap_seconds', 0.0):.3f} "
              f"restores={st.get('restore', 0)} "
              f"avg_snapshot_s={secs/max(snaps, 1):.3f} "
              f"degraded={health['degraded']}")
        if st.get("persist_upload_bytes"):
            print(f"[{args.backend}] uploads="
                  f"{st['persist_upload_bytes'] / 1e6:.1f}MB "
                  f"upload_s={st.get('persist_upload_seconds', 0.0):.3f} "
                  f"retries={st.get('persist_upload_retries', 0)} "
                  f"throttle_s="
                  f"{st.get('persist_throttle_seconds', 0.0):.3f}")
        if st.get("delta_flights") or st.get("keyframe_flights"):
            print(f"[{args.backend}] "
                  f"delta_flights={st.get('delta_flights', 0)} "
                  f"keyframes={st.get('keyframe_flights', 0)} "
                  f"skipped_buckets={st.get('skipped_buckets', 0)} "
                  f"base_misses={st.get('delta_base_misses', 0)}")
        if st.get("scrub_passes"):
            print(f"[{args.backend}] scrub_passes={st['scrub_passes']} "
                  f"families={st.get('scrub_families', 0)} "
                  f"corrupt={st.get('scrub_corrupt', 0)} "
                  f"repaired={st.get('scrub_repaired', 0)}")
    for node in health["degraded"]:
        err = health["members"].get(node, {}).get("error")
        print(f"[degraded] member {node} lost its saving sidecar: "
              f"{err or 'SMP not alive'}", file=sys.stderr)
    rc = 1 if health["degraded"] else 0
    if not losses:
        print(f"[done] steps={step} (resumed past --steps; nothing to run) "
              f"wall={time.time()-t0:.1f}s")
        return rc
    print(f"[done] steps={step} final_loss={losses[-1]:.4f} "
          f"first_loss={losses[0]:.4f} wall={time.time()-t0:.1f}s")
    assert np.isfinite(losses).all(), "loss diverged"
    if args.steps >= 100:                 # short smoke runs are too noisy
        assert np.mean(losses[-10:]) < np.mean(losses[:10]), \
            "loss did not decrease"
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
