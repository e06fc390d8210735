"""The training traffic: one general window loop for every training mix.

The loop is the one `repro.launch.train.main` runs (batch -> jitted step
-> `float(loss)` -> `CheckpointSession.after_step`), bounded by the clock
instead of a step count, with injected failures restored in place as
`launch/train.py` restores them.  What a mix sets (`bench/traffic/<traffic>.json`):

  backend         "reft" or "null"
  snapshot_every  steps between snapshot requests
  failure         null, or {"kind": ..., "after": "publish"}: after every
                  SG publish, fail member (seed + i) mod n mid-flight,
                  restore, heal and train on
  count           what `attempted`/`failed` count: flights|restores|steps
  keep_published  also hold the state of each member's last published
                  step (a restore may land there)

Set-up builds one compiled step and one state, drives the first three
steps through the window's own loop (their loss, first gradient and
weight change are what the reference checks), waits for the first flight
to publish, and hands that same state to the window.
"""
from __future__ import annotations

import gc
import math
import os
import shutil
import tempfile
import time

import numpy as np

import jax
import jax.numpy as jnp

import correct
import harness


class BatchStream:
    """Step-indexed, restartable token stream from the seed (uniform
    tokens and labels over the vocabulary), the same sequence as the
    program's `SyntheticDataset` for the same seed."""

    def __init__(self, ref, conf, seed, batch, seq):
        self.ref, self.conf = ref, conf
        self.seed, self.batch, self.seq = seed, batch, seq
        self._step = 0

    def state(self) -> dict:
        return {"seed": self.seed, "step": self._step}

    def restore(self, st: dict):
        self.seed, self._step = int(st["seed"]), int(st["step"])

    def host(self, step: int):
        return self.ref.host_batch(self.conf, self.seed, step, self.batch,
                                   self.seq)

    def __next__(self):
        tok, lab = self.host(self._step)
        self._step += 1
        return {"tokens": jnp.asarray(tok), "labels": jnp.asarray(lab)}


class StateKeeper:
    """Device states the checks will need, by step: each member's newest
    flight (the flight pins it anyway) and, where a restore may land
    there, each member's last published step."""

    def __init__(self, keep_published: bool):
        self.keep_published = keep_published
        self.states = {}

    def offer(self, step: int, state):
        self.states.setdefault(step, state)

    def prune(self, watch, engines):
        want = set(watch.newest_steps().values())
        if self.keep_published:
            want |= {e.last_clean_step for e in engines}
        for s in list(self.states):
            if s not in want:
                del self.states[s]


class GcWatch:
    """Python's garbage collections while it is on, to tell a collection
    from other host stalls of the window (read, not compared)."""

    def __init__(self):
        self.pauses, self._t0 = [], 0.0

    def __call__(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.pauses.append((time.perf_counter() - self._t0,
                                info["generation"]))


def _fault_step(fault, step_fn, ref, conf, opt):
    """The timed path broken on purpose (rehearsal checks of the check)."""
    if fault == "stale_state":
        def stale(state, batch):
            _, m = step_fn(state, batch)
            return state, m
        return stale
    if fault == "half_batch":
        def half(state, batch):
            b = batch["tokens"].shape[0] // 2
            return step_fn(state, {k: v[:b] for k, v in batch.items()})
        return half
    if fault == "control":
        @jax.jit
        def control(state, batch):
            p, o = state["params"], state["opt_state"]
            loss, g = ref.loss_and_grads(conf, p, batch["tokens"],
                                         batch["labels"], fp8=True)
            t = o["step"] + 1
            new, mu, nu, _ = ref.adamw(opt, g, o["mu"], o["nu"], p, t)
            return ({"params": new, "opt_state": {"mu": mu, "nu": nu,
                                                  "step": t},
                     "step": state["step"] + 1, "rng": state["rng"]},
                    {"loss": loss})
        return control
    return step_fn


def run(ctx: dict) -> dict:
    from jax.profiler import TraceAnnotation

    from repro.api import CheckpointSession, CheckpointSpec
    from repro.train.steps import make_train_step

    conf, cfg, cell, mix = ctx["conf"], ctx["cfg"], ctx["cell"], ctx["mix"]
    seed, seconds, fault = ctx["seed"], ctx["seconds"], ctx.get("fault")
    ref = harness.load_module("reference", conf["reference"])
    batch, seq = cell["batch"], cell["seq"]
    n = conf["sg_size"]
    reft = mix["backend"] == "reft"
    counter = ctx["counter"]
    rec = {"tokens_per_step": batch * seq, "steps": [], "restores": [],
           "flights": [], "checks": {}, "notes": []}

    state = ref.init_state(conf, seed)
    stream = BatchStream(ref, conf, seed, batch, seq)
    step_fn = jax.jit(make_train_step(cfg, harness.adam_config(conf)))
    step_fn = _fault_step(fault, step_fn, ref, conf, conf["optimizer"])
    norms = jax.jit(ref.leaf_norms)
    change = jax.jit(lambda a, b: ref.leaf_norms(jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b)))

    ckpt_dir = tempfile.mkdtemp(prefix="bench-ckpt-")
    spec = CheckpointSpec(backend=mix["backend"], ckpt_dir=ckpt_dir,
                          sg_size=n, resume=False,
                          run_id=CheckpointSpec.alloc_run_id(),
                          snapshot_every_steps=mix.get("snapshot_every", 1),
                          checkpoint_every_steps=10 ** 9)
    ctx["run_id"] = spec.run_id           # the harness unlinks its segments
    print(f"[bench] run id {spec.run_id}", flush=True)
    if reft:
        need = harness.shm_needed(sum(x.nbytes for x in
                                      jax.tree.leaves(state)), n,
                                  8 * spec.bucket_bytes)
        free = harness.shm_free()
        if free < need:
            raise RuntimeError(f"/dev/shm has {free} bytes free, the SG's "
                               f"SMPs need {need}")
    sess = CheckpointSession(spec, state)
    engines = (lambda: sess.checkpointer.group.engines) if reft \
        else (lambda: [])
    watch = harness.FlightWatch()
    keeper = StateKeeper(mix.get("keep_published", False))
    cur = {"state": state, "step": 0}
    del state

    def one_step():
        t0 = time.perf_counter()
        with TraceAnnotation("bench.batch"):
            b = next(stream)
        with TraceAnnotation("bench.step"):
            st, m = step_fn(cur["state"], b)
            loss = float(m["loss"])
        cur["state"], cur["step"] = st, cur["step"] + 1
        with TraceAnnotation("bench.after_step"):
            sess.after_step(st, cur["step"], extra_meta=stream.state())
        if reft:
            watch.poll(engines(), t0)
            for node, s in watch.newest_steps().items():
                if s == cur["step"]:
                    keeper.offer(s, st)
            keeper.prune(watch, engines())
        t1 = time.perf_counter()
        rec["steps"].append((t0, t1, math.isfinite(loss)))
        return loss

    def fail_and_restore(i):
        node = (seed + i) % n
        t_inj = time.perf_counter()
        with TraceAnnotation("bench.inject"):
            sess.inject(mix["failure"]["kind"], node=node, graceful=False)
        with TraceAnnotation("bench.restore"):
            res = sess.restore()
        want = keeper.states.get(res.step)
        if want is None:
            rec["notes"].append(f"restored step {res.step} not held; held "
                                f"{sorted(keeper.states)}")
        cur["state"] = jax.tree.map(jnp.asarray, res.state)
        stream.restore(res.extra_meta)
        cur["step"] = res.step
        one_step()
        t1 = time.perf_counter()
        got = res.state
        if fault == "flip_restore":
            leaf = jax.tree.leaves(got)[0]
            leaf.reshape(-1).view(np.uint8)[0] ^= 0xFF
        mism = -1 if want is None else correct.tree_mismatch(got, want)
        ld = res.load
        rec["restores"].append({
            "node": node, "step": res.step, "tier": res.tier,
            "t_inject": t_inj, "t_end": t1, "resume_s": t1 - t_inj,
            "read_s": ld.read_seconds if ld else None,
            "decode_s": ld.decode_seconds if ld else None,
            "h2d_s": ld.h2d_seconds if ld else None,
            "decoded_bytes": ld.decoded_bytes if ld else None,
            "mismatch": mism})
        del res, got, want
        return cur["step"]

    gcw = GcWatch()
    try:
        # ---- set-up: the first three steps, through the window's loop
        p0 = cur["state"]["params"]
        losses = [one_step()]
        mu_norms = [float(x) for x in
                    norms(cur["state"]["opt_state"]["mu"])]
        losses += [one_step(), one_step()]
        change_norms = [float(x) for x in change(cur["state"]["params"],
                                                 p0)]
        del p0
        rec["prog"] = {"loss": losses, "mu_norms": mu_norms,
                       "change_norms": change_norms}
        sess.wait()                       # the first flight is published
        if reft:
            watch.poll(engines(), time.perf_counter())
            keeper.prune(watch, engines())
        n_fail = 0
        rec["steps"].clear()
        watch.done.clear()
        gc.collect()

        # ---- the window
        compiles0 = counter.n
        trace_dir = None
        if ctx.get("trace"):
            trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        gc.callbacks.append(gcw)
        t_w0 = time.perf_counter()
        rec["setup_s"] = t_w0 - ctx["t_process"]
        # the set-up's published flight counts: the first failure comes at
        # the first step of the window, mid-flight of the next one
        armed = min((e.last_clean_step for e in engines()), default=0)
        with TraceAnnotation("bench.window"):
            while time.perf_counter() - t_w0 < seconds:
                one_step()
                if fault == "crash":
                    raise RuntimeError("crash planted in the window")
                if mix.get("failure") and \
                        min(e.last_clean_step for e in engines()) >= armed:
                    armed = fail_and_restore(n_fail)
                    n_fail += 1
        t_w1 = time.perf_counter()
        gc.callbacks.remove(gcw)
        if trace_dir:
            jax.profiler.stop_trace()
        rec["window"] = (t_w0, t_w1)
        rec["window_s"] = t_w1 - t_w0
        rec["compiles_in_window"] = counter.n - compiles0
        rec["trace_dir"] = trace_dir

        # ---- after the window: every flight lands, then the checks
        sess.drain()
        if reft:
            watch.finish()
        rec["flights"] = watch.done
        dev = jax.devices()[0]
        stats = dev.memory_stats() or {}
        rec["memory_peak_bytes"] = stats.get("peak_bytes_in_use")
        if reft:
            if fault == "flip_snapshot":
                e = engines()[0]
                idx = correct.published_steps(sess.run_id, e.node)[
                    e.last_clean_step]
                path = os.path.join(correct.SHM,
                                    f"reft-{sess.run_id}-n0-buf{idx}")
                with open(path, "r+b") as f:
                    b0 = f.read(1)
                    f.seek(0)
                    f.write(bytes([b0[0] ^ 0xFF]))
            streams, bad, checked = {}, 0, 0
            for e in engines():
                s = e.last_clean_step
                if s not in keeper.states:
                    bad += 1
                    continue
                if s not in streams:
                    streams[s] = correct.state_bytes(keeper.states[s])
                m = correct.snapshot_mismatch(sess.run_id, e.node, n, s,
                                              streams[s])
                bad += 1 if m < 0 else m
                checked += 1
            rec["checks"]["snapshot_mismatch_bytes"] = bad
            rec["snapshot_checked"] = checked
            del streams
        if mix.get("failure"):
            rs = rec["restores"]
            rec["checks"]["restore_mismatch_bytes"] = sum(
                (1 if r["mismatch"] < 0 else r["mismatch"]) for r in rs)
            rec["checks"]["restores_not_in_memory"] = sum(
                r["tier"] not in ("in-memory", "raim5") for r in rs)
    finally:
        if gcw in gc.callbacks:               # a window that raised
            gc.callbacks.remove(gcw)
        sess.close(final_persist=False)
        harness.unlink_segments(sess.run_id)
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    # ---- free the program's state, then follow with the reference
    del cur, keeper
    gc.collect()
    batches = [stream.host(s) for s in range(3)]
    t_ref = time.perf_counter()
    refn = ref.follow(conf, seed, batches)
    rec["reference_s"] = time.perf_counter() - t_ref
    gaps = correct.train_gaps(rec["prog"], refn, conf["optimizer"])
    rec["checks"].update(loss_gap=gaps["loss_gap"],
                         grad_gap=gaps["grad_gap"],
                         update_gap=gaps["update_gap"])
    steps_s = sorted(t1 - t0 for t0, t1, _ in rec["steps"])
    rec["info"] = {"leaves_left_out": gaps["leaves_left_out"],
                   "worst_grad_leaf": gaps["worst_grad_leaf"],
                   "worst_update_leaf": gaps["worst_update_leaf"],
                   "median_step_s": steps_s[len(steps_s) // 2]
                   if steps_s else None,
                   "longest_step_s": steps_s[-1] if steps_s else None,
                   "gc_in_window": len(gcw.pauses),
                   "gc_longest_s_gen": max(gcw.pauses, default=None)}
    rec["ref_loss"] = refn["loss"]
    _count(rec, mix, seconds)
    return rec


def _count(rec, mix, seconds):
    t_w0, t_w1 = rec["window"]
    kind = mix.get("count", "steps")
    if kind == "flights":
        fl = [f for f in rec["flights"] if t_w0 <= f["t_start"] < t_w1]
        rec["attempted"] = len(fl)
        rec["failed"] = sum(not f["ok"] for f in fl)
    elif kind == "restores":
        rs = rec["restores"]
        rec["attempted"] = len(rs)
        rec["failed"] = sum(r["mismatch"] != 0 or r["tier"] not in
                            ("in-memory", "raim5") for r in rs)
    else:
        rec["attempted"] = len(rec["steps"])
        rec["failed"] = sum(not ok for _, _, ok in rec["steps"])
