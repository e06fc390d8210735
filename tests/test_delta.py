"""Dirty-delta snapshotting (ISSUE 7).

Units: range algebra, dirty planning, keyframe policy, persist-chain
log, MoE touch tracking, FSDP/EP sharding rules, chain-aware GC.
Integration (real SMP shards): delta-chain restore byte-identity vs the
full-snapshot oracle (host AND device encode), keyframe forcing at the
dirty-fraction threshold, elastic n->m resume from a delta family, and
the scrubber repairing a corrupt delta object / file.
"""
import glob
import os
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.coordinator import ReftGroup
from repro.core.delta import (
    DeltaLog, DeltaTracker, expert_dirty_ranges, merge_ranges,
    ranges_intersect, task_dirty,
)
from repro.core.recovery import (
    delta_families, latest_checkpoint_step, resolve_chain,
    restorable_steps, restore_from_checkpoint, restore_state,
)
from repro.core.snapshot import ReftConfig, SnapshotEngine
from repro.core.treebytes import make_flat_spec


def trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb))


def mkstate(n_leaves=4, shape=(32, 64), seed=0):
    rng = np.random.RandomState(seed)
    return {f"w{i}": jnp.asarray(rng.rand(*shape), jnp.float32)
            for i in range(n_leaves)}


# ================================================================ units
def test_merge_ranges_and_intersect():
    assert merge_ranges([(5, 10), (0, 6), (20, 20), (12, 14)]) == \
        [(0, 10), (12, 14)]
    r = merge_ranges([(0, 10), (20, 30)])
    assert ranges_intersect(r, 5, 6)
    assert ranges_intersect(r, 9, 25)        # spans the gap
    assert ranges_intersect(r, 29, 100)
    assert not ranges_intersect(r, 10, 20)   # exactly the hole
    assert not ranges_intersect(r, 30, 40)
    assert not ranges_intersect(r, 3, 3)     # empty probe
    assert not ranges_intersect([], 0, 10)


def test_task_dirty_own_and_fused_parity():
    own = SimpleNamespace(kind=0, lo=100, hi=200, sources=None)
    par = SimpleNamespace(kind=2, lo=0, hi=64,
                          sources=[(300, 400), (500, 600)])
    dirty = merge_ranges([(150, 160)])
    assert task_dirty(own, dirty)
    assert not task_dirty(par, dirty)
    # parity refreshes when ANY source block slice moved
    assert task_dirty(par, merge_ranges([(550, 551)]))
    assert not task_dirty(own, merge_ranges([(550, 551)]))


def test_expert_dirty_ranges_stacked_vs_dense():
    E = 4
    spec = make_flat_spec({
        "router": jnp.zeros((8,), jnp.float32),
        "wi_gate": jnp.zeros((E, 2, 2), jnp.float32),
    })
    by_name = {l.path: l for l in spec.leaves}
    gate = next(v for k, v in by_name.items() if "wi_gate" in k)
    router = next(v for k, v in by_name.items() if "router" in k)
    per = gate.nbytes // E
    got = expert_dirty_ranges(spec, [False, True, False, True])
    want = merge_ranges([
        (router.offset, router.offset + router.nbytes),  # dense: whole leaf
        (gate.offset + 1 * per, gate.offset + 2 * per),
        (gate.offset + 3 * per, gate.offset + 4 * per),
    ])
    assert got == want
    # every expert touched == everything dirty
    allr = expert_dirty_ranges(spec, [True] * E)
    assert allr == [(0, spec.total_bytes)]


def test_expert_dirty_ranges_held_share_on_the_stacked_axis():
    """A chip holding experts 4..7 of 8, under the layer stack (expert axis
    1 of (periods, E_held, ...)): the global touched mask picks the held
    experts' slices in every period; experts held elsewhere dirty
    nothing here."""
    L, E, Eh = 2, 8, 4
    spec = make_flat_spec({"blocks": {"pos0": {"ffn": {
        "router": jnp.zeros((L, 3, E), jnp.float32),
        "wi_gate": jnp.zeros((L, Eh, 2, 2), jnp.float32)}}}})
    gate = next(l for l in spec.leaves if "wi_gate" in l.path)
    router = next(l for l in spec.leaves if "router" in l.path)
    per = gate.nbytes // (L * Eh)
    touched = [True, True, False, False, False, True, False, True]
    got = expert_dirty_ranges(spec, touched, held=range(4, 8))
    want = merge_ranges(
        [(router.offset, router.offset + router.nbytes)]
        + [(gate.offset + (i * Eh + e) * per,
            gate.offset + (i * Eh + e + 1) * per)
           for i in range(L) for e in (1, 3)])      # global ids 5 and 7
    assert got == want
    # touched only elsewhere: the expert leaf stays clean
    assert expert_dirty_ranges(spec, [True] * 4 + [False] * 4,
                               held=range(4, 8)) == \
        [(router.offset, router.offset + router.nbytes)]


def test_delta_tracker_policy():
    sched = [SimpleNamespace(kind=0, lo=0, hi=10, sources=None),
             SimpleNamespace(kind=0, lo=10, hi=100, sources=None)]
    t = DeltaTracker(keyframe_every=2, dirty_threshold=0.5)
    assert t.plan(0, sched, None, 100) is None        # no base digests yet
    t.commit(3, {0: 11, 1: 22}, was_delta=False, sent_frac=1.0)
    fd = t.plan(3, sched, None, 100)
    assert fd is not None and fd.base_step == 3 and fd.prev == {0: 11, 1: 22}
    assert t.plan(4, sched, None, 100) is None        # base rotated away
    # dirty fraction above threshold -> keyframe; below -> skip clean tasks
    assert t.plan(3, sched, [(0, 60)], 100) is None
    fd = t.plan(3, sched, [(0, 5)], 100)
    assert fd is not None and fd.skip == frozenset({1})
    # keyframe_every flights since last full -> keyframe
    t.commit(4, {0: 1, 1: 2}, was_delta=True, sent_frac=0.1)
    assert t.plan(4, sched, None, 100) is not None
    t.commit(5, {0: 1, 1: 2}, was_delta=True, sent_frac=0.1)
    assert t.plan(5, sched, None, 100) is None
    # a delta that turned out dense forces the next keyframe
    t2 = DeltaTracker(keyframe_every=100, dirty_threshold=0.5)
    t2.commit(1, {0: 1}, was_delta=False, sent_frac=1.0)
    t2.commit(2, {0: 1}, was_delta=True, sent_frac=0.9)
    assert t2.plan(2, sched, None, 100) is None
    # invalidate drops the base entirely
    t2.invalidate()
    assert t2.base_step == -1 and t2.digests is None


def test_delta_log_extents_since():
    log = DeltaLog()
    log.record(0, None)                      # keyframe
    log.record(1, [(0, 10)])
    log.record(2, [(5, 20), (30, 40)])
    assert log.extents_since(0, 2) == [(0, 20), (30, 40)]
    assert log.extents_since(1, 2) == [(5, 20), (30, 40)]
    assert log.extents_since(2, 2) is None   # step <= base
    assert log.extents_since(-1, 2) is None
    assert log.extents_since(7, 9) is None   # unknown base
    log.record(3, None)                      # keyframe voids the chain
    assert log.extents_since(1, 3) is None
    log.record(4, [])
    assert log.extents_since(3, 4) == []     # nothing changed: empty delta
    small = DeltaLog(cap=2)
    for s in range(3):
        small.record(s, [(s, s + 1)])
    assert 0 not in small.entries            # trimmed
    assert small.extents_since(0, 2) is None


def test_expert_touch_tracker():
    from repro.models.moe import ExpertTouchTracker
    t = ExpertTouchTracker()
    t.record([[0, 1]])                       # disabled: no-op
    t.enable(8)
    t.record(np.array([[1, 3], [5, 1]]))
    t.record(np.array([[99, -2]]))           # out-of-range ids filtered
    mask = t.consume()
    assert mask.tolist() == [False, True, False, True, False, True,
                             False, False]
    assert not t.consume().any()             # consume resets
    t.disable()
    t.record(np.array([[2]]))
    assert not t.peek().any()


def test_shardings_fsdp_and_ep_rules():
    from repro.dist.shardings import param_specs
    shapes = {
        "wi_gate": jax.ShapeDtypeStruct((4, 16, 32), jnp.float32),
        "wo": jax.ShapeDtypeStruct((4, 32, 16), jnp.float32),
        "wq": jax.ShapeDtypeStruct((16, 32), jnp.float32),
        "scale": jax.ShapeDtypeStruct((), jnp.float32),
    }
    # EP + FSDP: experts over "model", fan-in over the batch axes
    cfg = SimpleNamespace(moe_ep=True, num_experts=4, fsdp=True)
    sp = param_specs(cfg, shapes)
    assert sp["wi_gate"] == P("model", ("pod", "data"), None)
    assert sp["wo"] == P("model", ("pod", "data"), None)
    assert sp["wq"] == P(("pod", "data"), "model")    # FSDP fills the
    assert sp["scale"] == P()                         # replicated dim
    # EP without FSDP
    cfg = SimpleNamespace(moe_ep=True, num_experts=4, fsdp=False)
    sp = param_specs(cfg, shapes)
    assert sp["wi_gate"] == P("model", None, None)
    assert sp["wq"] == P(None, "model")
    # expert-count mismatch falls back to the plain table
    cfg = SimpleNamespace(moe_ep=True, num_experts=8, fsdp=False)
    sp = param_specs(cfg, shapes)
    assert sp["wi_gate"] == P(None, None, "model")
    assert sp["wo"] == P(None, "model", None)


# =========================================================== chain + GC
def _touch_family(d, step, nodes, base=None):
    for node in nodes:
        name = (f"step-{step}-node-{node}.reft" if base is None else
                f"step-{step}-from-{base}-node-{node}.reftd")
        open(os.path.join(d, name), "wb").close()


def test_resolve_chain_and_restorable_steps(tmp_path):
    d = str(tmp_path)
    _touch_family(d, 0, [0, 1])
    _touch_family(d, 4, [0, 1], base=0)
    _touch_family(d, 8, [0, 1], base=4)
    _touch_family(d, 9, [0, 1], base=7)      # dangling base
    assert resolve_chain(d, 0) == (0, [])
    assert resolve_chain(d, 8) == (0, [(4, 0), (8, 4)])
    assert resolve_chain(d, 9) is None
    assert restorable_steps(d, 2) == [0, 4, 8]
    assert latest_checkpoint_step(d, 2) == 8
    assert set(delta_families(d)) == {4, 8, 9}
    # torn link poisons every dependent
    os.remove(os.path.join(d, "step-4-from-0-node-1.reftd"))
    assert restorable_steps(d, 2) == [0]
    assert latest_checkpoint_step(d, 2) == 0


def test_plan_gc_keyframe_liveness_and_cascade():
    from repro.ckpt.manager import plan_gc
    fam = {0: None, 4: None, 8: None}
    deps = {4: 0, 8: 4}
    # keeping the chain head keeps its whole ancestry alive
    assert plan_gc(fam, {0, 4, 8}, {8}, deps=deps) == []
    # keeping only the keyframe lets the deltas go
    assert sorted(plan_gc(fam, {0, 4, 8}, {0}, deps=deps)) == [4, 8]
    # a torn middle link cascades: the dependent is dead weight too
    assert sorted(plan_gc(fam, {0, 8}, {8}, deps=deps)) == [4, 8]
    # without deps the old flat policy is unchanged
    assert plan_gc(fam, {0, 4, 8}, {0, 4, 8}) == []


def test_manager_gc_spares_delta_ancestry(tmp_path):
    from repro.ckpt.manager import CheckpointManager
    d = str(tmp_path)
    _touch_family(d, 0, [0, 1])
    _touch_family(d, 4, [0, 1], base=0)
    _touch_family(d, 8, [0, 1], base=4)
    mgr = CheckpointManager(d, 2, keep=1)
    assert mgr.complete_steps() == [0, 4, 8]
    assert mgr.latest() == 8
    mgr.commit()
    # keep=1 keeps step 8 — but its keyframe + middle link must survive
    assert restorable_steps(d, 2) == [0, 4, 8]
    # tear the middle link: dependents stop being restorable, the torn
    # remnant is GC'd (newest torn family is spared as possibly
    # in-flight), and latest falls back to the keyframe
    os.remove(os.path.join(d, "step-4-from-0-node-1.reftd"))
    assert mgr.complete_steps() == [0]
    assert mgr.latest() == 0
    mgr.commit()
    assert not glob.glob(os.path.join(d, "step-4-*"))
    assert restorable_steps(d, 2) == [0]


# ====================================================== SMP integration
def _persist_round(g, d, n, remote=None):
    assert g.checkpoint_async(
        remote=remote,
        delta_base=latest_checkpoint_step(d, n)) is not None
    r = g.drain_persists()[-1]
    assert r["ok"], r
    return r


@pytest.mark.parametrize("device_encode", ["off", "on"])
def test_delta_chain_restore_matches_full_oracle(device_encode, tmp_path):
    """keyframe + delta chain restores byte-identically to the state the
    full-snapshot path would have captured, on both encode paths."""
    d = str(tmp_path)
    cfg = ReftConfig(ckpt_dir=d, bucket_bytes=2048, delta=True,
                     delta_keyframe=8, delta_dirty_threshold=0.9,
                     device_encode=device_encode,
                     checkpoint_every_snapshots=10 ** 9)
    g = ReftGroup(2, mkstate(), cfg)
    states, kinds = {}, []
    st = mkstate()
    try:
        for step in range(4):
            st = dict(st)
            st["w1"] = st["w1"] + (step + 1)
            states[step] = st
            assert g.snapshot(st, step, wait=True)
            kinds.append(_persist_round(g, d, 2)["kind"])
        assert g.engines[0].stats["delta_flights"] >= 1
        assert g.engines[0].stats["skipped_buckets"] > 0   # S1: clean
    finally:                                               # buckets skip
        g.close()
    assert kinds == ["full", "delta", "delta", "delta"]
    assert restorable_steps(d, 2) == [0, 1, 2, 3]
    for step, want in states.items():
        got, at, _ = restore_from_checkpoint(d, 2, mkstate(), step=step)
        assert at == step and trees_equal(got, want)


def test_keyframe_forced_at_dirty_threshold_and_shm_identity():
    """A provider reporting most bytes dirty forces a keyframe (delta
    saves nothing dense); a sparse provider yields a delta flight whose
    published shm shard is still byte-identical to the live state."""
    state = {"a": jnp.zeros((4096,), jnp.float32),
             "b": jnp.ones((4096,), jnp.float32)}
    cfg = ReftConfig(bucket_bytes=2048, delta=True, delta_keyframe=100,
                     delta_dirty_threshold=0.05,
                     checkpoint_every_snapshots=10 ** 9)
    eng = SnapshotEngine(0, 1, state, cfg)
    dirty = [None]
    eng.set_dirty_provider(lambda: dirty[0])
    try:
        total = eng.spec.total_bytes
        assert eng.snapshot_sync(state, 1) == 1      # first: keyframe
        dirty[0] = [(0, total)]                      # dense -> keyframe
        assert eng.snapshot_sync(state, 2) == 2
        assert eng.stats["keyframe_flights"] == 2
        assert eng.stats["delta_flights"] == 0
        state2 = dict(state)
        state2["a"] = state["a"].at[:8].set(7.0)     # sparse real change
        dirty[0] = [(0, 64)]
        assert eng.snapshot_sync(state2, 3) == 3
        assert eng.stats["delta_flights"] == 1
        assert eng.stats["skipped_buckets"] > 0
        rec, at, _ = restore_state(eng.run, 1, total, state, [0])
        assert at == 3 and trees_equal(rec, state2)
    finally:
        eng.close()


@pytest.mark.parametrize("device_encode", ["off", "on"])
def test_two_of_eight_dirty_experts_send_at_most_half_the_bytes(
        device_encode, tmp_path):
    """An 8-expert MoE state whose router reports 2 experts touched: a
    delta flight sends at most half the bytes of a full flight of the same
    state and bucket geometry (bytes only: they are deterministic)."""
    from repro.api import CheckpointSpec
    E, dirty_experts, d = 8, 2, 64
    rng = np.random.RandomState(0)

    def mk(*sh):
        return jnp.asarray(rng.rand(*sh), jnp.float32)

    state = {"router": mk(256, E), "wi_gate": mk(E, d, d), "wo": mk(E, d, d)}
    touched = [i < dirty_experts for i in range(E)]
    mutated = dict(state)
    for k in ("wi_gate", "wo"):
        mutated[k] = state[k].at[:dirty_experts].add(1.0)
    sent = {}
    for mode, opts in (("full", {}),
                       ("delta", {"delta": True, "delta_keyframe": 10 ** 6,
                                  "delta_dirty_threshold": 0.9})):
        spec = CheckpointSpec(backend="reft", ckpt_dir=str(tmp_path / mode),
                              sg_size=2, bucket_bytes=4096, resume=False,
                              options={**opts,
                                       "device_encode": device_encode})
        with spec.build(state) as ck:
            if mode == "delta":
                fspec = ck.group.engines[0].spec
                ck.set_dirty_provider(
                    lambda: expert_dirty_ranges(fspec, touched))
            assert ck.snapshot(state, 1, wait=True)   # delta: the keyframe
            b0 = ck.stats()["engine_bytes_sent"]
            assert ck.snapshot(mutated, 2, wait=True)
            st = ck.stats()
            sent[mode] = st["engine_bytes_sent"] - b0
            if mode == "delta":
                assert st["delta_flights"] == 2       # one per member
            res = ck.restore()
            assert res.step == 2 and trees_equal(res.state, mutated)
    assert 0 < sent["delta"] <= 0.5 * sent["full"], sent


def test_delta_family_elastic_resume_and_local_scrub(tmp_path):
    """n=3 delta family: elastic resume into a 5-member SG from a delta
    step, then the scrubber detects + repairs a corrupted `.reftd`."""
    from repro.store.scrub import _head_off, scrub_local_dir
    d = str(tmp_path)
    cfg = ReftConfig(ckpt_dir=d, bucket_bytes=4096, delta=True,
                     delta_keyframe=8, delta_dirty_threshold=0.9,
                     checkpoint_every_snapshots=10 ** 9)
    g = ReftGroup(3, mkstate(8, (64, 64)), cfg)
    states = {}
    st = mkstate(8, (64, 64))
    try:
        for step in range(3):
            st = dict(st)
            st["w2"] = st["w2"] + (step + 1)
            states[step] = st
            assert g.snapshot(st, step, wait=True)
            _persist_round(g, d, 3)
    finally:
        g.close()
    # elastic: the 3-member delta family restores into a 5-member SG
    got, at, _ = restore_from_checkpoint(d, 5, mkstate(8, (64, 64)), step=2)
    assert at == 2 and trees_equal(got, states[2])
    # corrupt one delta shard's payload; scrub repairs it in place
    path = os.path.join(d, "step-2-from-1-node-1.reftd")
    off = _head_off(path)
    with open(path, "r+b") as f:
        f.seek(off)
        f.write(b"\xff" * 32)
    reports = {r.step: r for r in scrub_local_dir(d, repair=True)}
    assert reports[2].kind == "chain"
    assert reports[2].corrupt and reports[2].repaired
    assert not reports[2].unrepairable and not reports[2].errors
    assert all(r.clean for r in scrub_local_dir(d, repair=True))
    got, at, _ = restore_from_checkpoint(d, 3, mkstate(8, (64, 64)), step=2)
    assert at == 2 and trees_equal(got, states[2])


def test_delta_objstore_chain_restore_and_scrub(tmp_path):
    """Tier-4: delta manifests chain by base_step, the remote restore
    walks the chain, and the object scrubber repairs a corrupt delta
    object through the serving layer."""
    from repro.core.recovery import restore_from_objstore
    from repro.store import (
        LocalObjectStore, build_manifest, put_manifest, scrub_object_store,
    )
    from repro.store.manifest import load_manifest, manifest_base_step
    d = str(tmp_path)
    store = LocalObjectStore(os.path.join(d, "obj"))
    remote = {"store": store.config, "prefix": "families"}
    cfg = ReftConfig(ckpt_dir=d, bucket_bytes=4096, delta=True,
                     delta_keyframe=8, delta_dirty_threshold=0.9,
                     checkpoint_every_snapshots=10 ** 9)
    g = ReftGroup(3, mkstate(8, (64, 64)), cfg)
    states = {}
    st = mkstate(8, (64, 64))
    try:
        for step in range(3):
            st = dict(st)
            st["w2"] = st["w2"] + (step + 1)
            states[step] = st
            assert g.snapshot(st, step, wait=True)
            r = _persist_round(g, d, 3, remote=remote)
            man = build_manifest(g.run, r["step"], 3, g.total_bytes,
                                 r["uploads"])
            put_manifest(store, "families", man)
            assert man["kind"] == r["kind"]
    finally:
        g.close()
    man2 = load_manifest(store, "families", 2)
    assert man2["kind"] == "delta" and manifest_base_step(man2) == 1
    got, at, _ = restore_from_objstore(store, "families", 3,
                                       mkstate(8, (64, 64)), step=2)
    assert at == 2 and trees_equal(got, states[2])
    # corrupt a delta object's payload and scrub-repair it
    ent = man2["nodes"][1]
    blob = bytearray(store.read(ent["key"]))
    doff = int(ent["data_off"])
    blob[doff:doff + 64] = b"\xff" * 64
    store.put(ent["key"], bytes(blob))
    reports = {r.step: r for r in scrub_object_store(store, "families",
                                                     repair=True)}
    assert reports[2].kind == "chain"
    assert reports[2].corrupt and reports[2].repaired
    assert not reports[2].unrepairable and not reports[2].errors
    assert all(r.clean for r in scrub_object_store(store, "families",
                                                   repair=True))
    got, at, _ = restore_from_objstore(store, "families", 3,
                                       mkstate(8, (64, 64)), step=2)
    assert at == 2 and trees_equal(got, states[2])


def test_leaf_extents_and_ranged_reader():
    """`leaf_extents` covers every plan range with element-aligned
    per-leaf extents, and a `LeafReader` restricted to those extents
    reads byte-identically to an unrestricted one."""
    from repro.core.pipeline import LeafReader, leaf_budget, leaf_extents
    state = mkstate(3, (16, 32))                 # 3 leaves x 2048 bytes
    spec = make_flat_spec(state)
    leaves = jax.tree.leaves(state)
    # ranges: tail of leaf 0, hole, slice inside leaf 2 (unaligned ends)
    ranges = [(1500, 2100), (4197, 4199)]
    ext = leaf_extents(spec, ranges)
    assert set(ext) == {0, 1, 2}
    for i, (lo, hi) in ext.items():
        ls = spec.leaves[i]
        assert 0 <= lo < hi <= ls.nbytes
        assert lo % 4 == 0 and (hi % 4 == 0 or hi == ls.nbytes)
    a, b = ext[2]
    assert a <= 4197 - 4096 and b >= 4199 - 4096 and b - a <= 12
    plain = LeafReader(spec, leaves)
    ranged = LeafReader(spec, leaves, leaf_budget(spec, ranges), ext)
    for lo, hi in ranges:
        want = np.empty(hi - lo, np.uint8)
        got = np.empty(hi - lo, np.uint8)
        plain.read(lo, hi, want)
        ranged.read(lo, hi, got)
        assert np.array_equal(want, got)


def test_ranged_fetch_delta_flight_identity():
    """With `ranged_fetch="on"` (forced device-side extent slicing, the
    real-accelerator path) a sparse delta flight still publishes a shard
    byte-identical to the live state."""
    state = {"a": jnp.zeros((4096,), jnp.float32),
             "b": jnp.ones((4096,), jnp.float32)}
    cfg = ReftConfig(bucket_bytes=2048, delta=True, delta_keyframe=100,
                     delta_dirty_threshold=0.9, ranged_fetch="on",
                     checkpoint_every_snapshots=10 ** 9)
    eng = SnapshotEngine(0, 1, state, cfg)
    dirty = [None]
    eng.set_dirty_provider(lambda: dirty[0])
    try:
        assert eng.snapshot_sync(state, 1) == 1      # keyframe
        state2 = dict(state)
        state2["a"] = state["a"].at[16:24].set(5.0)
        dirty[0] = [(64, 96)]
        assert eng.snapshot_sync(state2, 2) == 2
        assert eng.stats["delta_flights"] == 1
        assert eng.stats["skipped_buckets"] > 0
        rec, at, _ = restore_state(eng.run, 1, eng.spec.total_bytes,
                                   state, [0])
        assert at == 2 and trees_equal(rec, state2)
    finally:
        eng.close()
