#!/usr/bin/env python3
"""The readings the train-step limits are set from, for one cell.

  python3 bench/readings.py --workload <cell> --seeds 1,2,3 [--reduced]

For each seed, in one process (one compilation): the program's first
three steps through its jitted step from the seed's state and rows (as
the window loop drives them), the float32 reference, the fp8 control (the
reference with every matrix product's operands rounded to float8_e4m3fn),
and the reference fed half of each batch (the half-batch fault).  Prints
one JSON line per seed with the gaps of each against the reference
(`correct.train_gaps`); the largest program gap over a dozen seeds is a
limit's lower reading, the smallest control or fault gap its upper one.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def program_stepper(cfg, ref, adam):
    """The program's jitted step and the leaf-norm readers, built once."""
    import jax
    import jax.numpy as jnp
    from repro.train.steps import make_train_step
    step_fn = jax.jit(make_train_step(cfg, adam))
    norms = jax.jit(ref.leaf_norms)
    change = jax.jit(lambda a, b: ref.leaf_norms(jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b)))
    return step_fn, norms, change


def program_numbers(conf, ref, seed, batches, stepper):
    import jax.numpy as jnp
    step_fn, norms, change = stepper
    state = ref.init_state(conf, seed)
    p0 = state["params"]
    losses, mu = [], None
    for tok, lab in batches:
        state, m = step_fn(state, {"tokens": jnp.asarray(tok),
                                   "labels": jnp.asarray(lab)})
        losses.append(float(m["loss"]))
        if mu is None:
            mu = [float(x) for x in norms(state["opt_state"]["mu"])]
    dp = [float(x) for x in change(state["params"], p0)]
    return {"loss": losses, "mu_norms": mu, "change_norms": dp}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--reduced", action="store_true")
    args = ap.parse_args(argv)
    import jax
    import correct
    import harness
    if not args.reduced:
        if jax.devices()[0].platform != "tpu":
            print("readings: needs a TPU", file=sys.stderr)
            return 3
        from repro.launch.cache import use_compile_cache
        use_compile_cache()
    cell = harness.load_json("workloads", args.workload + ".json")
    conf = harness.load_json("configs", cell["config"] + ".json")
    cfg, conf = harness.program_config(conf, reduced=args.reduced)
    if args.reduced:
        cell = dict(cell, **cell.get("reduced", {}))
    ref = harness.load_module("reference", conf["reference"])
    stepper = program_stepper(cfg, ref, harness.adam_config(conf))
    opt = conf["optimizer"]
    B, S = cell["batch"], cell["seq"]
    for seed in (int(s) for s in args.seeds.split(",")):
        batches = [ref.host_batch(conf, seed, s, B, S) for s in range(3)]
        f32 = ref.follow(conf, seed, batches)
        fp8 = ref.follow(conf, seed, batches, fp8=True)

        def mu_of(r):
            return {"loss": r["loss"],
                    "mu_norms": [g * (1 - opt["b1"])
                                 for g in r["grad_norms"]],
                    "change_norms": r["change_norms"]}
        prog = program_numbers(conf, ref, seed, batches, stepper)
        gc.collect()
        half = ref.follow(conf, seed, [(t[:B // 2], lab[:B // 2])
                                       for t, lab in batches])
        out = {"seed": seed, "workload": args.workload,
               "program": correct.train_gaps(prog, f32, opt),
               "control_fp8": correct.train_gaps(mu_of(fp8), f32, opt),
               "half_batch": correct.train_gaps(mu_of(half), f32, opt),
               "loss": {"program": prog["loss"], "reference": f32["loss"],
                        "fp8": fp8["loss"], "half": half["loss"]}}
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
