"""Real-process failure injection: SIGKILL trainers/SMPs, unlink shared
memory, recover bit-exact (the paper's §6 restart experiment in miniature).
"""
import numpy as np
import pytest

from repro.core.cluster import LocalCluster


def bitexact(a, b):
    import jax
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


@pytest.fixture
def cluster(tmp_path):
    c = LocalCluster(4, seed=11, nbytes=1 << 15, snapshot_every=1,
                     ckpt_dir=str(tmp_path))
    yield c
    c.close()


def test_software_failure_inmemory_resume(cluster):
    c = cluster
    c.run_rounds(4)
    c.kill_trainer(2)                       # SIGKILL; SMP orphaned alive
    state, step, tier = c.recover()
    assert tier == "in-memory" and step == 4
    assert bitexact(state, c.expected_state(step))
    c.restart_node(2, state)
    c.run_rounds(2)                         # cluster proceeds healthily
    assert c.nodes[2].last_step == 6


def test_node_failure_raim5_decode(cluster):
    c = cluster
    c.run_rounds(3)
    c.kill_node(1)                          # trainer+SMP dead, memory wiped
    state, step, tier = c.recover()
    assert tier == "raim5" and step == 3
    assert bitexact(state, c.expected_state(step))


def test_double_failure_falls_back_to_ckpt(cluster):
    c = cluster
    c.run_rounds(3)
    c.checkpoint()
    c.run_rounds(2)
    c.kill_node(0)
    c.kill_node(3)
    state, step, tier = c.recover()
    assert tier == "checkpoint" and step == 3     # ckpt taken at step 3
    assert bitexact(state, c.expected_state(step))


def test_smp_only_crash_keeps_training(cluster):
    """SMP dies but trainer lives: training continues; protection is
    degraded until heal (we just assert no training disruption)."""
    c = cluster
    c.run_rounds(2)
    c.kill_smp(3)
    c.run_rounds(2)                          # rounds still complete
    assert all(np_.last_step == 4 for np_ in c.nodes.values())


NO_BACKEND = """
import os
if __name__ == "__main__":
    import jax
    jax.devices()                            # this process holds the backend
    os.environ["JAX_PLATFORMS"] = "nosuchplatform"   # every child inherits
    from repro.core.cluster import LocalCluster
    c = LocalCluster(2, seed=1, nbytes=1 << 14, snapshot_every=1,
                     ckpt_dir={ckpt!r})
    try:
        c.run_rounds(3)
        c.checkpoint()
        c.kill_trainer(1)
        state, step, tier = c.recover()
    finally:
        c.close()
    print("CHILDREN_OK", tier, step)
"""


def test_spawned_children_initialise_no_jax_backend(tmp_path):
    """Node processes and their SMPs run with a platform that cannot be
    initialised: any backend init in a child (the chip held twice, on a
    TPU host) would kill that child and stall the rounds."""
    import os
    import subprocess
    import sys
    script = tmp_path / "nobackend.py"      # spawn re-imports __main__
    script.write_text(NO_BACKEND.format(ckpt=str(tmp_path / "ck")))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(
        os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=300)
    assert "CHILDREN_OK in-memory 3" in out.stdout, out.stderr[-3000:]
