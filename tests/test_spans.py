"""Program spans (`repro.core.spans`) and what they time: the HASC levels
of a flight, the facade's `snapshot-published` events, and the train
step's named scopes."""
import glob
import re
import tempfile
import threading
import time

import pytest

import jax
import jax.numpy as jnp

from repro.core.pipeline import LEVELS, step_boundary
from repro.core.snapshot import ReftConfig, SnapshotEngine
from repro.core.spans import span


def opt_state(n=1 << 14):
    k = jax.random.PRNGKey(0)
    return {"a_params": {"w": jax.random.normal(k, (n,), jnp.float32)},
            "opt": {"mu": jnp.zeros((n,), jnp.float32),
                    "nu": jnp.ones((n,), jnp.float32)}}


def traced(fn):
    """Run `fn` under a CPU profiler trace; returns (fn's value, host
    events as {line index: [(name, start_ns, dur_ns, {stat: value})]})."""
    from jax.profiler import ProfileData
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                lines[i] = [(e.name, e.start_ns, e.duration_ns,
                             {k: v for k, v in e.stats})
                            for e in line.events]
    return out, lines


def test_span_adds_to_its_counter_and_records_its_metadata():
    counter = {"l2": 0.0}

    def work():
        with span("repro.test.outer"):
            with span("repro.test.send", counter, "l2", bytes=4096):
                time.sleep(0.01)
    _, lines = traced(work)
    assert counter["l2"] >= 0.01
    evs = {n: (d, st) for line in lines.values() for n, _, d, st in line}
    assert evs["repro.test.send"][1] == {"bytes": 4096}
    assert evs["repro.test.send"][0] / 1e9 == pytest.approx(counter["l2"],
                                                            abs=2e-3)
    assert evs["repro.test.outer"][0] >= evs["repro.test.send"][0]


def test_span_counts_a_body_that_raises():
    counter = {"k": 0.0}
    with pytest.raises(ValueError):
        with span("repro.test.fail", counter, "k"):
            time.sleep(0.002)
            raise ValueError("body")
    assert counter["k"] >= 0.002


@pytest.mark.parametrize("device_encode", ["off", "on"])
def test_flight_splits_l1_into_dispatch_and_d2h(device_encode):
    state = opt_state()
    eng = SnapshotEngine(0, 2, state, ReftConfig(
        bucket_bytes=4096, device_encode=device_encode,
        yield_every_buckets=1, ckpt_dir=tempfile.mkdtemp()))
    try:
        t0 = time.perf_counter()
        eng.snapshot_async(state, 1)
        flight = eng._flight
        while flight.in_flight():           # a live trainer: the pump
            step_boundary()                 # yields at step boundaries
            time.sleep(0.001)
        r = flight.wait()
        assert r.l1_seconds == r.l1_dispatch_seconds + r.l1_d2h_seconds
        assert r.l1_dispatch_seconds > 0 and r.l1_d2h_seconds > 0
        assert r.l1_gate_seconds >= 0
        assert t0 <= r.t_start <= r.t_published
        assert r.wall_seconds == r.t_published - r.t_start
        assert set(r.levels()) == set(LEVELS)
        eng.wait()
        assert eng.stats["l1_dispatch_seconds"] == r.l1_dispatch_seconds
        assert list(eng.published) == [r]
    finally:
        eng.close()


def test_flight_spans_are_on_the_trace():
    """The pump and stager threads open their spans on their own lines;
    the launch is on the caller's; the sent bytes ride on the L2 spans."""
    state = opt_state()
    eng = SnapshotEngine(0, 2, state, ReftConfig(
        bucket_bytes=4096, device_encode="on", ckpt_dir=tempfile.mkdtemp()))
    try:
        def one():
            with span("repro.test.main"):
                eng.snapshot_async(state, 1)
            eng.wait()
            return eng.stats["bytes_sent"]
        sent, lines = traced(one)
    finally:
        eng.close()
    where = {}
    for li, evs in lines.items():
        for n, _, _, _ in evs:
            where.setdefault(n, set()).add(li)
    (main,) = where["repro.test.main"]
    assert where["repro.hasc.launch"] == {main}
    for name in ("repro.hasc.l1.dispatch", "repro.hasc.l1.d2h",
                 "repro.hasc.l1.credit", "repro.hasc.l2.send",
                 "repro.hasc.l3.begin", "repro.hasc.l3.publish"):
        assert where[name] and main not in where[name], name
    assert sum(st["bytes"] for evs in lines.values()
               for n, _, _, st in evs if n == "repro.hasc.l2.send") == sent


def test_snapshot_published_once_per_member_flight():
    from repro.api import CheckpointSpec
    state = opt_state(1 << 12)
    with tempfile.TemporaryDirectory() as d:
        spec = CheckpointSpec(backend="reft", ckpt_dir=d, sg_size=2,
                              resume=False, bucket_bytes=1 << 12)
        with spec.build(state) as ck:
            for step in range(1, 5):
                ck.snapshot(state, step)
                time.sleep(0.05)
            ck.wait()
            flights = sum(e.stats["snapshots"] for e in ck.group.engines)
            ev = [e for e in ck.events if e.kind == "snapshot-published"]
            assert flights >= 2 and len(ev) == flights
            assert ck.stats()["snapshot-published"] == flights
            assert sorted(e.detail for e in ev) == sorted(
                f"node{e.node}" for e in ck.group.engines
                for _ in range(e.stats["snapshots"]))
            for e in ev:
                assert 1 <= e.step <= 4 and e.nbytes > 0
                assert e.t_start <= e.t_published
                assert set(e.levels) == set(LEVELS)


def test_train_step_hlo_names_its_scopes():
    from repro.configs import get_config
    from repro.train.steps import init_train_state, make_train_step
    cfg = get_config("opt-125m").reduced()
    state = jax.eval_shape(lambda: init_train_state(cfg, 0).tree())
    batch = {k: jax.ShapeDtypeStruct((2, 64), jnp.int32)
             for k in ("tokens", "labels")}
    hlo = jax.jit(make_train_step(cfg)).lower(state, batch).as_text(
        dialect="hlo", debug_info=True)
    names = re.findall(r'op_name="([^"]*)"', hlo)
    for scope in ("attention", "optimizer", "mlp", "embed", "head_loss"):
        assert any(re.search(rf"(^|[/(]){scope}[/)]", n) for n in names), \
            scope


def test_a_span_is_recorded_on_its_own_thread():
    """A span is per thread: one opened on another thread is not nested
    in the caller's."""
    counter = {"a": 0.0, "b": 0.0}

    def other():
        with span("repro.test.b", counter, "b"):
            time.sleep(0.003)

    def work():
        with span("repro.test.a", counter, "a"):
            t = threading.Thread(target=other)
            t.start()
            t.join()
    _, lines = traced(work)
    where = {n: li for li, evs in lines.items() for n, _, _, _ in evs
             if n.startswith("repro.test.")}
    assert where["repro.test.a"] != where["repro.test.b"]
    assert counter["a"] >= counter["b"] >= 0.003
