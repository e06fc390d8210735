"""HASC saving pipeline: schedule ordering, interference, backpressure,
wait-timeout semantics, leaf-cache eviction, per-level accounting,
device-side encode equivalence, multi-flight overlap, saving-path
affinity."""
import os
import tempfile
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.pipeline import (
    LeafReader, StepBoundaryGate, build_schedule, leaf_budget,
    resolve_affinity, step_boundary,
)
from repro.core.snapshot import ReftConfig, SnapshotEngine
from repro.core.treebytes import make_flat_spec


def opt_state(n=1 << 14, seed=0):
    """params + adam moments, moments deliberately NOT first in flatten
    order (dict order: mu/nu sort after params? flatten order is key-sorted
    -> 'mu' < 'nu' < 'params'; use explicit names to pin params first)."""
    k = jax.random.PRNGKey(seed)
    return {
        "a_params": {"w": jax.random.normal(k, (n,), jnp.float32),
                     "b": jnp.ones((257,), jnp.bfloat16)},
        "opt": {"mu": jnp.zeros((n,), jnp.float32),
                "nu": jnp.zeros((n,), jnp.float32)},
        "rng": jax.random.PRNGKey(seed + 1),
    }


def trees_equal(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ------------------------------------------------------------- scheduling
def test_bucket_schedule_opt_first():
    state = opt_state()
    spec = make_flat_spec(state)
    own = [(0, 0, spec.total_bytes)]
    sched = build_schedule(spec, own, [], 4096, opt_first=True)
    # all bytes covered exactly once
    covered = sorted((t.lo, t.hi) for t in sched)
    assert covered[0][0] == 0 and covered[-1][1] == spec.total_bytes
    assert all(a2 == b1 for (_, b1), (a2, _) in zip(covered, covered[1:]))
    # optimizer-moment buckets drain first
    flags = [t.opt for t in sched]
    assert any(flags), "schedule found no optimizer leaves"
    assert not any(flags[flags.index(False):]), \
        "a non-opt bucket precedes an opt bucket"
    # and the opt buckets really point at moment leaves
    first = sched[0]
    assert "opt" in spec.leaves[first.leaf_lo].path.lower()


def test_bucket_schedule_unordered_matches_plan_order():
    state = opt_state()
    spec = make_flat_spec(state)
    own = [(0, 0, spec.total_bytes)]
    sched = build_schedule(spec, own, [], 4096, opt_first=False)
    los = [t.lo for t in sched]
    assert los == sorted(los)


def test_leaf_budget_counts_all_plan_bytes():
    state = opt_state()
    spec = make_flat_spec(state)
    budget = leaf_budget(spec, [(0, spec.total_bytes)])
    assert sum(budget.values()) == spec.total_bytes
    half = spec.total_bytes // 2
    budget2 = leaf_budget(spec, [(0, half)])
    assert sum(budget2.values()) == half


# --------------------------------------------------------------- reader
def test_leaf_reader_evicts_consumed_leaves():
    state = opt_state()
    spec = make_flat_spec(state)
    budget = leaf_budget(spec, [(0, spec.total_bytes)])
    r = LeafReader(spec, jax.tree_util.tree_leaves(state), budget)
    out = np.empty(4096, np.uint8)
    for lo in range(0, spec.total_bytes, 4096):
        hi = min(lo + 4096, spec.total_bytes)
        r.read(lo, hi, out[:hi - lo])
    assert r.cached_leaves() == 0, "host cache not evicted after consumption"


def test_leaf_reader_unbudgeted_keeps_cache():
    state = opt_state()
    spec = make_flat_spec(state)
    r = LeafReader(spec, jax.tree_util.tree_leaves(state))
    out = np.empty(spec.total_bytes, np.uint8)
    r.read(0, spec.total_bytes, out)
    assert r.cached_leaves() == len(spec.leaves)


# ------------------------------------------------------------ interference
@pytest.mark.parametrize("pipelined", [True, False])
def test_training_steps_proceed_while_snapshot_in_flight(pipelined):
    state = {"opt_mu": jnp.zeros((1 << 18,), jnp.float32),
             "w": jnp.ones((1 << 18,), jnp.float32)}
    eng = SnapshotEngine(0, 1, state,
                         ReftConfig(pipeline=pipelined, bucket_bytes=1 << 12,
                                    stage_slots=4))
    try:
        assert eng.snapshot_async(state, 1)
        steps_during_flight = 0
        deadline = time.monotonic() + 30
        while eng.in_flight() and time.monotonic() < deadline:
            # a "training step": touch the accelerator state, tick the gate
            _ = float(jnp.sum(state["w"][:16]))
            step_boundary()
            steps_during_flight += 1
        assert steps_during_flight > 0, \
            "no training step completed while the snapshot was in flight"
        assert eng.wait() == 1
        from repro.core.recovery import restore_state
        rec, step, _ = restore_state(eng.run, 1, eng.spec.total_bytes,
                                     state, [0])
        assert step == 1 and trees_equal(rec, state)
    finally:
        eng.close()


# ------------------------------------------------------------ backpressure
def test_backpressure_ring_full_stalls_without_data_loss():
    """stage ring of 1 slot + tiny buckets: L1 must stall on credits while
    the SMP drains; the snapshot still completes bit-identically."""
    state = opt_state(1 << 12)
    cfg = ReftConfig(bucket_bytes=512, stage_slots=1, scratch_buffers=2)
    eng = SnapshotEngine(0, 1, state, cfg)
    try:
        assert eng.snapshot_async(state, 7)
        assert eng.wait() == 7
        assert eng.stats["l1_stall_seconds"] >= 0.0
        assert eng.stats["bytes_sent"] >= eng.spec.total_bytes
        from repro.core.recovery import restore_state
        rec, step, _ = restore_state(eng.run, 1, eng.spec.total_bytes,
                                     state, [0])
        assert step == 7 and trees_equal(rec, state)
    finally:
        eng.close()


def test_sg4_pipelined_snapshot_raim5_roundtrip():
    """Full SG with parity stripes through the pipeline: single-node loss
    still decodes bit-identically (recovery contract unchanged)."""
    from repro.core import ReftGroup
    import tempfile
    state = opt_state(1 << 12)
    cfg = ReftConfig(bucket_bytes=512, stage_slots=4,
                     ckpt_dir=tempfile.mkdtemp(),
                     checkpoint_every_snapshots=10 ** 6)
    g = ReftGroup(4, state, cfg)
    try:
        g.snapshot(state, 3, extra_meta={"k": 3})
        g.inject_node_failure(2)
        rec, step, extra, tier = g.recover()
        assert tier == "raim5" and step == 3 and extra == {"k": 3}
        assert trees_equal(rec, state)
        lv = g.level_seconds()
        assert lv["l1"] > 0 and lv["l2"] > 0 and lv["l3"] > 0
    finally:
        g.close()


def test_finished_flight_and_closed_group_pin_no_state():
    """A published flight drops its device leaves (the trainer's state
    goes when the trainer drops it), and a closed group drops its
    template: after training nothing of the saving path holds a state
    on the device."""
    import gc
    from repro.core import ReftGroup
    state = opt_state(1 << 12, seed=3)
    cfg = ReftConfig(bucket_bytes=512, stage_slots=4,
                     ckpt_dir=tempfile.mkdtemp(),
                     checkpoint_every_snapshots=10 ** 6)
    g = ReftGroup(2, state, cfg)
    try:
        g.snapshot(state, 1)
        g.wait()
        flights = [e._pipeline._last for e in g.engines]
        assert all(f is not None and not f.in_flight() for f in flights)
        assert all(f.leaves is None and f.encoder is None for f in flights)
    finally:
        g.close()
    assert g.template is None
    ids = {id(x) for x in jax.tree.leaves(state)}
    del state
    gc.collect()
    assert not ids & {id(x) for x in jax.live_arrays()}


# ------------------------------------------------------- wait() semantics
@pytest.mark.parametrize("pipelined", [True, False])
def test_wait_timeout_keeps_flight_live(pipelined):
    """Satellite fix: a timed-out wait() must NOT drop the handle — a
    second snapshot can never overlap a live one."""
    state = {"opt_mu": jnp.zeros((1 << 19,), jnp.float32)}
    eng = SnapshotEngine(0, 1, state,
                         ReftConfig(pipeline=pipelined, bucket_bytes=1 << 11,
                                    stage_slots=2))
    try:
        assert eng.snapshot_async(state, 1)
        with pytest.raises(TimeoutError):
            eng.wait(timeout=0.001)
        # the flight is still owned: a second snapshot is refused, and a
        # patient wait() drains the ORIGINAL flight
        assert not eng.snapshot_async(state, 2)
        assert eng.wait() == 1
        assert eng.stats["snapshots"] == 1
    finally:
        eng.close()


def test_recovery_decodes_single_laggard_member():
    """A member whose async rounds lag (buffer rotation evicted the steps
    its peers still hold) is equivalent to one failed node at the newest
    step: recovery must RAIM5-decode its shard, not fall through to the
    (possibly empty) checkpoint tier."""
    from repro.core import ReftGroup
    import tempfile
    state = opt_state(1 << 12)
    cfg = ReftConfig(bucket_bytes=1024, stage_slots=4,
                     ckpt_dir=tempfile.mkdtemp(),
                     checkpoint_every_snapshots=10 ** 6)
    g = ReftGroup(4, state, cfg)
    try:
        g.snapshot(state, 2, extra_meta={"k": 2})       # all members
        # member 0 lags: only the others complete rounds 4, 6, 8, so their
        # 3-buffer rotation evicts step 2 — no step is clean on ALL four
        for s in (4, 6, 8):
            st = jax.tree.map(
                lambda x, s=s: x + s if x.dtype != jnp.uint32 else x, state)
            for e in g.engines[1:]:
                assert e.snapshot_async(st, s, {"k": s})
            for e in g.engines[1:]:
                e.wait()
        last = jax.tree.map(lambda x: x + 8 if x.dtype != jnp.uint32 else x,
                            state)
        rec, step, extra, tier = g.recover()
        assert step == 8 and tier == "raim5" and extra == {"k": 8}
        assert trees_equal(rec, last)
    finally:
        g.close()


def test_single_node_corrupt_newest_falls_back_to_older_step():
    """n==1 with a CRC-corrupt newest snapshot must fall back to the older
    clean step (never pick a step with zero usable sources), and raise
    RecoveryError — not crash — when every step is corrupt."""
    from repro.core.recovery import RecoveryError, restore_state
    from tests.test_integrity_and_policy import _corrupt_clean_buffer
    state = opt_state(1 << 10)
    eng = SnapshotEngine(0, 1, state, ReftConfig(bucket_bytes=2048))
    try:
        eng.snapshot_sync(state, 1)
        st2 = jax.tree.map(lambda x: x + 1 if x.dtype != jnp.uint32 else x,
                           state)
        eng.snapshot_sync(st2, 2)
        assert _corrupt_clean_buffer(eng.run, 0, 1, eng.spec.total_bytes) == 2
        rec, step, _ = restore_state(eng.run, 1, eng.spec.total_bytes,
                                     state, [0])
        assert step == 1 and trees_equal(rec, state)
        # corrupt the older step too -> every candidate has zero usable
        # sources -> clean RecoveryError (tier 3 takes over), not a crash
        _corrupt_clean_buffer_at(eng.run, 0, 1, eng.spec.total_bytes)
        with pytest.raises(RecoveryError):
            restore_state(eng.run, 1, eng.spec.total_bytes, state, [0])
    finally:
        eng.close()


def _corrupt_clean_buffer_at(run, node, step, total_bytes):
    from repro.core.smp import ReadOnlyNode, _attach, _seg
    view = ReadOnlyNode(run, node, 1, total_bytes)
    idx = view.clean_steps()[step]
    view.close()
    shm = _attach(_seg(run, node, f"buf{idx}"))
    shm.buf[100] = (shm.buf[100] + 1) % 256
    shm.close()


def test_smp_death_mid_flight_degrades_not_wedges():
    """SMP killed mid-flight with a tiny ring: the stager must not block
    forever on ring credits the dead SMP can never release — the engine
    degrades and training-side calls keep returning."""
    state = {"opt_mu": jnp.zeros((1 << 18,), jnp.float32)}
    eng = SnapshotEngine(0, 1, state,
                         ReftConfig(bucket_bytes=1 << 11, stage_slots=1))
    try:
        assert eng.snapshot_async(state, 1)
        eng.smp.proc.kill()                   # not via inject: state stays
        step = eng.wait(timeout=60)           # returns, does NOT wedge
        assert eng.degraded
        assert step == -1                     # nothing ever became clean
        assert not eng.snapshot_async(state, 2)
    finally:
        eng.close()


def test_flight_internal_timeout_degrades_not_wedges():
    """A flight that FAILS with an internal TimeoutError (SMP ack timeout)
    is a dead flight: the engine must degrade — like the serial path —
    not keep the corpse as 'still live' and wedge every later call."""
    state = opt_state(1 << 10)
    eng = SnapshotEngine(0, 1, state, ReftConfig(bucket_bytes=1 << 12))
    try:
        def _ack_timeout(timeout=60.0):
            raise TimeoutError("SMP ack timeout (simulated)")
        eng.smp.wait_clean = _ack_timeout
        assert eng.snapshot_async(state, 1)
        assert eng.wait() == -1          # no clean step; no exception
        assert eng.degraded
        assert eng._flight is None       # corpse collected, not kept live
        assert not eng.snapshot_async(state, 2)      # degraded: refused
    finally:
        eng.close()


# ------------------------------------------------------------- yield gate
def test_boundary_gate_inactive_without_trainer():
    g = StepBoundaryGate()
    assert not g.active()
    t0 = time.perf_counter()
    assert g.wait_boundary(0.5) is False        # returns immediately
    assert time.perf_counter() - t0 < 0.25
    g.notify()
    assert g.active()


def test_boundary_gate_releases_on_tick():
    import threading
    g = StepBoundaryGate()
    g.notify()                                  # mark active
    got = []
    t = threading.Thread(target=lambda: got.append(g.wait_boundary(5.0)))
    t.start()
    time.sleep(0.05)
    g.notify()
    t.join(timeout=5)
    assert got == [True]


# ----------------------------------------------------- device encode path
def test_device_encode_roundtrip_single_node():
    """device_encode="on" (interpret-mode kernels on CPU CI): snapshot ->
    restore is bit-identical, and the device-combined CRC satisfies
    recovery's verify_crc — a wrong digest would demote the only member
    to corrupt and the restore would raise."""
    state = opt_state(1 << 12)
    eng = SnapshotEngine(0, 1, state,
                         ReftConfig(bucket_bytes=2048, device_encode="on"))
    try:
        assert eng.stats["device_encode"] is True
        assert eng.snapshot_sync(state, 3) == 3
        from repro.core.recovery import restore_state
        rec, step, _ = restore_state(eng.run, 1, eng.spec.total_bytes,
                                     state, [0])
        assert step == 3 and trees_equal(rec, state)
    finally:
        eng.close()


def test_device_encode_byte_identical_to_host_path():
    """Host vs device encode of the SAME state must publish byte-identical
    own bytes, parity bytes, and own-region CRC — `raim5.decode_node` is
    encode-agnostic exactly because of this.  Odd bucket/leaf sizes
    exercise the padded-lane tails."""
    import pickle

    from repro.core import ReftGroup
    from repro.core.smp import ReadOnlyNode
    state = opt_state(1 << 12)
    probes = {}
    for mode in ("off", "on"):
        cfg = ReftConfig(bucket_bytes=768, stage_slots=4,
                         device_encode=mode, ckpt_dir=tempfile.mkdtemp(),
                         checkpoint_every_snapshots=10 ** 6)
        g = ReftGroup(3, state, cfg)
        try:
            assert g.snapshot(state, 2)
            view = ReadOnlyNode(g.run, 1, 3, g.total_bytes)
            try:
                probes[mode] = (view.read_own(2).tobytes(),
                                view.read_parity(2).tobytes(),
                                pickle.loads(view.meta(2))["crc_own"])
            finally:
                view.close()
        finally:
            g.close()
    assert probes["off"][0] == probes["on"][0], "own bytes differ"
    assert probes["off"][1] == probes["on"][1], "parity bytes differ"
    assert probes["off"][2] == probes["on"][2], "own-region CRC differs"


def _packed_share_of_schedule(spec, schedule):
    """(gather bytes, of them in 8/16-bit leaves) of one flight's
    schedule: every own bucket and every source of a fused parity one, up
    to the state's end (the stripe padding past it is zeros, not read)."""
    total = packed = 0
    for t in schedule:
        for lo, hi in (t.sources if t.kind == 2 else [(t.lo, t.hi)]):
            hi = min(hi, spec.total_bytes)
            total += max(0, hi - lo)
            for l in spec.leaves:
                if np.dtype(l.dtype).itemsize < 4:
                    packed += max(0, min(hi, l.offset + l.nbytes)
                                  - max(lo, l.offset))
    return total, packed


def test_device_encode_mixed_widths_byte_identical_and_packed_share():
    """A state of f32 and bf16 leaves (odd rows, a 1-D bf16 vector):
    the device path, whose bf16 pieces go through the pack kernel,
    publishes the host path's own bytes, parity bytes and CRC, and its
    counters give the schedule's 16-bit share (0 on the host path)."""
    import pickle

    from repro.core import ReftGroup
    from repro.core.smp import ReadOnlyNode
    k = jax.random.PRNGKey(7)
    state = {"emb": jax.random.normal(k, (37, 768), jnp.bfloat16),
             "mlp": jax.random.normal(k, (3, 7, 896), jnp.bfloat16),
             "norm": jnp.ones((769,), jnp.bfloat16),
             "opt": {"mu": jax.random.normal(k, (37, 768), jnp.float32),
                     "nu": jnp.zeros((3, 7, 896), jnp.float32)}}
    probes = {}
    for mode in ("off", "on"):
        cfg = ReftConfig(bucket_bytes=5000, stage_slots=4,
                         device_encode=mode, ckpt_dir=tempfile.mkdtemp(),
                         checkpoint_every_snapshots=10 ** 6)
        g = ReftGroup(3, state, cfg)
        try:
            assert g.snapshot(state, 2)
            view = ReadOnlyNode(g.run, 1, 3, g.total_bytes)
            try:
                probes[mode] = (view.read_own(2).tobytes(),
                                view.read_parity(2).tobytes(),
                                pickle.loads(view.meta(2))["crc_own"])
            finally:
                view.close()
            total = sum(e.stats["gather_bytes"] for e in g.engines)
            packed = sum(e.stats["gather_packed_bytes"] for e in g.engines)
            if mode == "off":
                assert total == packed == 0
            else:
                want = [_packed_share_of_schedule(e.spec,
                                                  e._pipeline.schedule)
                        for e in g.engines]
                assert total == sum(t for t, _ in want)
                assert packed == sum(p for _, p in want)
                assert 0 < packed < total
        finally:
            g.close()
    assert probes["off"] == probes["on"]


def test_sg4_device_encode_raim5_roundtrip():
    """Full SG with device-encoded (kind-2) parity: single-node loss still
    decodes bit-identically from the kernel-encoded parity blocks."""
    from repro.core import ReftGroup
    state = opt_state(1 << 12)
    cfg = ReftConfig(bucket_bytes=512, stage_slots=4,
                     ckpt_dir=tempfile.mkdtemp(),
                     checkpoint_every_snapshots=10 ** 6, device_encode="on")
    g = ReftGroup(4, state, cfg)
    try:
        assert g.snapshot(state, 3, extra_meta={"k": 3})
        # device path sends ONE encoded parity block, not n-1 stripe blocks
        assert g.engines[0].stats["bytes_sent"] < 2 * g.total_bytes / 4 * 1.5
        g.inject_node_failure(2)
        rec, step, extra, tier = g.recover()
        assert tier == "raim5" and step == 3 and extra == {"k": 3}
        assert trees_equal(rec, state)
    finally:
        g.close()


# --------------------------------------------------------- multi-flight
@pytest.mark.parametrize("device_encode", ["off", "on"])
def test_multi_flight_overlap_no_data_loss_bounded_scratch(device_encode):
    """max_flights=2: snapshot N+1 launches while N is still draining; both
    land bit-identically in the SMP triple buffer (no loss, no clobber)
    and the SHARED scratch pool never exceeds `scratch_buffers` credits."""
    state = {"opt_mu": jnp.zeros((1 << 15,), jnp.float32),
             "w": jnp.ones((1 << 15,), jnp.float32)}
    state2 = jax.tree.map(lambda x: x + 1, state)
    eng = SnapshotEngine(0, 1, state,
                         ReftConfig(bucket_bytes=1 << 12, stage_slots=4,
                                    max_flights=2, scratch_buffers=2,
                                    device_encode=device_encode))
    try:
        assert eng.snapshot_async(state, 1)
        assert eng.snapshot_async(state2, 2)          # overlapped launch
        assert not eng.snapshot_async(state2, 3)      # over the credit
        assert eng.wait() == 2
        assert eng.stats["snapshots"] == 2
        assert eng.stats["overlapped_flights"] >= 1
        pool = eng._pipeline
        assert pool._free.qsize() == pool.scratch_buffers   # fixed scratch
        from repro.core.recovery import restore_state
        from repro.core.smp import ReadOnlyNode
        from repro.core.treebytes import tree_to_buffer
        rec, step, _ = restore_state(eng.run, 1, eng.spec.total_bytes,
                                     state, [0])
        assert step == 2 and trees_equal(rec, state2)
        view = ReadOnlyNode(eng.run, 0, 1, eng.spec.total_bytes)
        try:
            assert {1, 2} <= set(view.clean_steps())
            flat1 = np.empty(eng.spec.total_bytes, np.uint8)
            tree_to_buffer(state, eng.spec, flat1)
            assert np.array_equal(
                view.read_own(1)[:eng.spec.total_bytes], flat1)
        finally:
            view.close()
    finally:
        eng.close()


# ------------------------------------------------------ batched leaf d2h
def test_leaf_reader_batched_fetch(monkeypatch):
    """Satellite: the prefetch window's leaves move host-side with ONE
    jax.device_get(list), not one synchronous np.asarray per leaf, and
    the result is byte-identical to the per-leaf path."""
    calls = []
    real = jax.device_get

    def counting(x):
        calls.append(len(x) if isinstance(x, list) else 1)
        return real(x)

    monkeypatch.setattr(jax, "device_get", counting)
    state = opt_state()
    spec = make_flat_spec(state)
    leaves = jax.tree_util.tree_leaves(state)
    r = LeafReader(spec, leaves)
    r.fetch(range(len(leaves)))
    assert calls == [len(leaves)] and r.batched_fetches == 1
    out = np.empty(spec.total_bytes, np.uint8)
    r.read(0, spec.total_bytes, out)
    assert calls == [len(leaves)], "read after fetch re-transferred leaves"
    r2 = LeafReader(spec, leaves)
    out2 = np.empty(spec.total_bytes, np.uint8)
    r2.read(0, spec.total_bytes, out2)
    assert np.array_equal(out, out2)


# ------------------------------------------------------- saving affinity
def test_affinity_resolution_best_effort():
    assert resolve_affinity(None) is None
    assert resolve_affinity("off") is None
    # malformed knobs degrade to None — never fail engine construction
    assert resolve_affinity("garbage") is None
    assert resolve_affinity(object()) is None
    if hasattr(os, "sched_getaffinity"):
        avail = sorted(os.sched_getaffinity(0))
        auto = resolve_affinity("auto")
        assert auto is None or set(auto) <= set(avail)
        assert resolve_affinity((avail[0],)) == (avail[0],)
        assert resolve_affinity(avail[0]) == (avail[0],)          # bare int
        got = resolve_affinity(",".join(str(c) for c in avail))   # "0,1"
        assert got == tuple(avail)
        assert resolve_affinity((10 ** 6,)) is None   # outside allowed set


def test_stager_affinity_surfaced_in_stats():
    if not hasattr(os, "sched_setaffinity"):
        pytest.skip("no sched_setaffinity on this platform")
    avail = sorted(os.sched_getaffinity(0))
    state = {"opt_mu": jnp.zeros((1 << 14,), jnp.float32)}
    eng = SnapshotEngine(0, 1, state,
                         ReftConfig(bucket_bytes=1 << 12,
                                    pin_cpus=(avail[-1],)))
    try:
        eng.snapshot_sync(state, 1)
        assert eng.stats["stager_affinity"] == (avail[-1],)
    finally:
        eng.close()


# ---------------------------------------------------------- facade events
def test_reft_backend_reports_levels():
    from repro.api import CheckpointSpec
    import tempfile
    state = opt_state(1 << 12)
    with tempfile.TemporaryDirectory() as d:
        spec = CheckpointSpec(backend="reft", ckpt_dir=d, sg_size=2,
                              resume=False, bucket_bytes=1 << 12)
        with spec.build(state) as ck:
            assert ck.snapshot(state, 1, wait=True)
            st = ck.stats()
            assert st["engine_l1_seconds"] > 0
            assert st["engine_l2_seconds"] > 0
            assert st["engine_l3_seconds"] > 0
            ev = [e for e in ck.events if e.kind == "snapshot"][-1]
            assert ev.levels is not None and ev.levels["l1"] > 0


def test_reft_backend_reports_gather_counters():
    """The device gather's counters reach the facade: each member's
    `snapshot-published` event carries its flight's, `stats()` their
    sums; the bf16 leaf's bytes are the packed ones."""
    from repro.api import CheckpointSpec
    import tempfile
    state = opt_state(1 << 12)
    with tempfile.TemporaryDirectory() as d:
        spec = CheckpointSpec(backend="reft", ckpt_dir=d, sg_size=2,
                              resume=False, bucket_bytes=1 << 12,
                              options={"device_encode": "on"})
        with spec.build(state) as ck:
            assert ck.snapshot(state, 1, wait=True)
            st = ck.stats()
            pub = [e for e in ck.events if e.kind == "snapshot-published"]
            assert len(pub) == 2
            assert st["engine_gather_bytes"] == sum(
                e.gather_bytes for e in pub) > 0
            assert st["engine_gather_packed_bytes"] == sum(
                e.gather_packed_bytes for e in pub) > 0
            assert st["engine_gather_packed_bytes"] < \
                st["engine_gather_bytes"]


def test_serial_fallback_via_options():
    from repro.api import CheckpointSpec
    import tempfile
    state = opt_state(1 << 12)
    with tempfile.TemporaryDirectory() as d:
        spec = CheckpointSpec(backend="reft", ckpt_dir=d, sg_size=2,
                              resume=False, bucket_bytes=1 << 12,
                              options={"pipeline": False})
        with spec.build(state) as ck:
            assert ck.group.engines[0]._pipeline is None
            assert ck.snapshot(state, 1, wait=True)
            res = ck.restore()
            assert res.step == 1 and trees_equal(res.state, state)
