"""REFT core: snapshot engine + SMP double-buffering + 3-tier recovery
(single-host process tree; real SMP processes)."""
import dataclasses
import os
import tempfile

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import NodeState, ReftConfig, ReftGroup
from repro.core.recovery import restore_state
from repro.core.smp import ReadOnlyNode
from repro.core.snapshot import SnapshotEngine


def small_state(seed=0):
    k = jax.random.PRNGKey(seed)
    return {
        "params": {"w": jax.random.normal(k, (64, 32)),
                   "b": jnp.ones((17,), jnp.bfloat16)},
        "opt": {"mu": jnp.zeros((64, 32)), "step": jnp.int32(0)},
        "rng": jax.random.PRNGKey(seed + 1),
    }


def trees_equal(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


@pytest.fixture
def group():
    state = small_state()
    cfg = ReftConfig(bucket_bytes=256, stage_slots=4,
                     ckpt_dir=tempfile.mkdtemp(),
                     checkpoint_every_snapshots=10 ** 6)
    g = ReftGroup(4, state, cfg)
    yield g, state
    g.close()


def test_snapshot_and_inmemory_restore(group):
    g, state = group
    g.snapshot(state, 1, extra_meta={"k": 1})
    st2 = jax.tree.map(lambda x: x + 1 if x.dtype != jnp.uint32 else x,
                       state)
    g.snapshot(st2, 2, extra_meta={"k": 2})
    g.inject_software_failure(0)
    rec, step, extra, tier = g.recover()
    assert tier == "in-memory" and step == 2 and extra == {"k": 2}
    assert trees_equal(rec, st2)


def test_snapshot_round_is_every_member_or_none(group, monkeypatch):
    """One member that cannot launch holds the whole round back, so the
    members never drift onto different steps (and a round pins one
    state, not one per member)."""
    g, state = group
    g.snapshot(state, 1)
    monkeypatch.setattr(g.engines[2], "ready", lambda: False)
    assert not g.snapshot(state, 2, wait=False)
    assert not any(e.in_flight() for e in g.engines)
    monkeypatch.undo()
    assert g.snapshot(state, 3)
    assert {e.last_clean_step for e in g.engines} == {3}


def test_raim5_tier_single_node_loss(group):
    g, state = group
    g.snapshot(state, 1)
    g.inject_node_failure(3)
    rec, step, extra, tier = g.recover()
    assert tier == "raim5" and step == 1
    assert trees_equal(rec, state)


def test_checkpoint_tier_double_loss(group):
    g, state = group
    g.snapshot(state, 1)
    g.checkpoint()
    g.inject_node_failure(0)
    g.inject_node_failure(2)
    rec, step, extra, tier = g.recover()
    assert tier == "checkpoint" and step == 1
    assert trees_equal(rec, state)


def test_dirty_snapshot_never_visible():
    """A snapshot without `end` must leave the previous clean intact
    (the dirty/clean double-buffer of §4.2)."""
    state = small_state()
    eng = SnapshotEngine(0, 1, state,
                         ReftConfig(bucket_bytes=128, stage_slots=2))
    try:
        eng.snapshot_sync(state, 1, {"v": 1})
        # partial write: begin + some buckets, no end
        from repro.core.treebytes import leaf_arrays
        eng.smp.begin(2)
        eng.smp.send_bucket(0, 0, np.zeros(64, np.uint8))
        view = ReadOnlyNode(eng.run, 0, 1, eng.spec.total_bytes)
        steps = view.clean_steps()
        assert 1 in steps and 2 not in steps
        assert view.latest_clean() == 1
        view.close()
        rec, step, extra = restore_state(eng.run, 1, eng.spec.total_bytes,
                                         state, [0])
        assert step == 1
        assert trees_equal(rec, state)
    finally:
        eng.close()


def test_multi_version_history():
    """Three buffers -> the two most recent clean steps stay addressable."""
    state = small_state()
    eng = SnapshotEngine(0, 1, state, ReftConfig(bucket_bytes=4096))
    try:
        for s in (1, 2, 3, 4):
            eng.snapshot_sync(jax.tree.map(
                lambda x: x + s if x.dtype != jnp.uint32 else x, state), s)
        view = ReadOnlyNode(eng.run, 0, 1, eng.spec.total_bytes)
        steps = sorted(view.clean_steps())
        view.close()
        assert 4 in steps and 3 in steps and 1 not in steps
    finally:
        eng.close()


def test_snapshot_async_overlaps_and_self_limits():
    state = {"w": jnp.zeros((1 << 16,), jnp.float32)}
    eng = SnapshotEngine(0, 1, state, ReftConfig(bucket_bytes=1 << 12))
    try:
        assert eng.snapshot_async(state, 1)
        # second call while in flight is refused, not queued (Figure 4)
        started = eng.snapshot_async(state, 2)
        eng.wait()
        assert eng.last_clean_step in (1, 2)
        if not started:
            assert eng.last_clean_step == 1
    finally:
        eng.close()


def test_heal_restores_full_protection(group):
    g, state = group
    g.snapshot(state, 1)
    g.inject_node_failure(1)
    rec, step, extra, tier = g.recover()
    assert tier == "raim5"
    g.heal(1)
    assert g.states[1] == NodeState.HEALTHY
    g.snapshot(state, 2)
    g.inject_node_failure(2)           # a *different* node can now fail
    rec, step, extra, tier = g.recover()
    assert tier == "raim5" and step == 2
    assert trees_equal(rec, state)
