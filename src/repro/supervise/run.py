"""Supervised training run: real model, injected failures, goodput report.

The supervisor-shaped sibling of `repro.launch.train`: same model/data/
step wiring, but the loop belongs to `repro.supervise.Supervisor` — it
fires a seeded scenario schedule (or explicit `--inject` specs), detects
and heals every fault (elastically resharding on `--elastic-to`), checks
each restore byte-exact against the oracle ring, and emits the
`BENCH_goodput.json` trajectory the CI goodput smoke gates on.

  PYTHONPATH=src python -m repro.supervise.run --arch opt-125m --reduced \\
      --steps 24 --sg-size 4 --scenarios 5 --seed 0 --elastic-to 2 \\
      --json BENCH_goodput.json --min-goodput 0.2

Exits non-zero on any unrecovered failure, any non-byte-exact restore,
a goodput fraction under `--min-goodput`, or ledger accounting that does
not sum to wall clock within 5%.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys

from repro.supervise.inject import (
    KINDS, Scenario, ensure_coverage, parse_scenario, plan_scenarios,
)

#: kinds a default CI smoke must cover (>=4 distinct, incl. a preempt)
SMOKE_KINDS = ("smp", "corrupt-stripe", "node", "preempt", "slow-persist")


def build_scenarios(args, sg: int) -> list:
    if args.inject:
        out = [parse_scenario(item) for item in args.inject]
    else:
        kinds = tuple(args.kinds.split(",")) if args.kinds else SMOKE_KINDS
        for k in kinds:
            if k not in KINDS:
                raise SystemExit(f"unknown kind {k!r}; want one of {KINDS}")
        out = plan_scenarios(args.seed, n=sg, total_steps=args.steps,
                             count=args.scenarios, kinds=kinds)
        out = ensure_coverage(out, kinds=kinds[:min(len(kinds), 4)], n=sg)
    if out and all(s.graceful for s in out):
        # the acceptance bar wants >=1 genuinely mid-flight injection
        out[0] = dataclasses.replace(out[0], graceful=False)
    if args.elastic_to:
        # the last scenario becomes the elastic reshard trigger
        last = out[-1]
        out[-1] = Scenario(kind="preempt", step=last.step, node=last.node,
                           graceful=last.graceful,
                           params={"new_sg": args.elastic_to})
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="opt-125m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=24)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--backend", default="reft",
                    choices=["reft", "objstore"])
    ap.add_argument("--sg-size", type=int, default=4)
    ap.add_argument("--snapshot-every", type=int, default=1)
    ap.add_argument("--ckpt-every", type=int, default=8)
    ap.add_argument("--ckpt-dir", default="/tmp/reft-supervised-ckpt")
    ap.add_argument("--auto-tune", action="store_true",
                    help="MTBF-fed Appendix-A cadence retuning")
    ap.add_argument("--scenarios", type=int, default=5,
                    help="number of seeded scenarios to plan")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kinds", default="",
                    help="comma-separated kind pool for the planner")
    ap.add_argument("--inject", action="append", default=[],
                    help="explicit STEP:KIND[:NODE] (overrides the "
                         "planner; repeatable)")
    ap.add_argument("--elastic-to", type=int, default=0,
                    help="reshard to this sg_size at the final scenario "
                         "(turns it into a preempt -> elastic rebuild)")
    ap.add_argument("--json", default="",
                    help="write the goodput trajectory here")
    ap.add_argument("--min-goodput", type=float, default=0.0,
                    help="fail the run under this goodput fraction")
    args = ap.parse_args(argv)

    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    import jax
    import jax.numpy as jnp

    from repro.api import CheckpointSpec
    from repro.configs import get_config
    from repro.configs.base import InputShape
    from repro.data.pipeline import SyntheticDataset
    from repro.supervise.supervisor import Supervisor
    from repro.train.steps import init_train_state, make_train_step

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    shape = InputShape("cli", args.seq, args.batch, "train")
    state = init_train_state(cfg, 0).tree()
    ds = SyntheticDataset(cfg, shape, seed=0)
    step_fn = jax.jit(make_train_step(cfg))

    def advance(st, step):
        st = jax.tree.map(jnp.asarray, st)     # restored trees are numpy
        st, _metrics = step_fn(st, next(ds))
        return st

    scenarios = build_scenarios(args, args.sg_size)
    print(f"[supervise] arch={cfg.name} params={cfg.param_count():,} "
          f"sg={args.sg_size} steps={args.steps} "
          f"scenarios={[(s.step, s.kind) for s in scenarios]}")

    spec = CheckpointSpec(
        backend=args.backend, ckpt_dir=args.ckpt_dir,
        sg_size=args.sg_size,
        snapshot_every_steps=args.snapshot_every,
        checkpoint_every_steps=args.ckpt_every,
        resume=False, auto_tune=args.auto_tune,
    )
    sup = Supervisor(spec, state, advance, scenarios=scenarios,
                     log=lambda s: print(s, flush=True))
    out = sup.run(args.steps)
    out.pop("final_state")

    g = out["goodput"]
    print(f"[supervise] failures={out['failures']} "
          f"kinds={out['kinds']} unrecovered={out['unrecovered']} "
          f"goodput={g['goodput_frac']:.3f} "
          f"acct_err={g['accounting_error']:.4f} "
          f"mtbf={out['mtbf_s']:.2f}s "
          f"lam_post={out['lam_node_posterior']:.2e}")
    for c, s in sorted(g["seconds"].items()):
        print(f"  {c:<17s} {s:8.3f}s  ({g['fractions'][c] * 100:5.1f}%)")

    if args.json:
        payload = dict(out)
        payload["config"] = {
            "arch": cfg.name, "sg_size": args.sg_size,
            "steps": args.steps, "seed": args.seed,
            "backend": args.backend,
            "scenarios": [dataclasses.asdict(s) for s in scenarios],
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2, default=str)
        print(f"[supervise] wrote {args.json}")

    ok = True
    if out["unrecovered"]:
        print(f"FAIL: {out['unrecovered']} unrecovered failures")
        ok = False
    bad_exact = [b for b in out["bit_exact_checks"] if b is False]
    if bad_exact:
        print(f"FAIL: {len(bad_exact)} restores were not byte-exact")
        ok = False
    if not (abs(g["accounting_error"]) <= 0.05):
        print(f"FAIL: ledger accounting error {g['accounting_error']:.4f} "
              f"> 5%")
        ok = False
    if g["goodput_frac"] < args.min_goodput:
        print(f"FAIL: goodput {g['goodput_frac']:.3f} < "
              f"{args.min_goodput}")
        ok = False
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
