"""Rehearsal of `chip_smoke.py` on the CPU at reduced size.

The script's phases are plain functions; here they run on CPU-resident
state (the host saving path) with the same verdicts the chip run applies,
minus the checks that only a TPU can answer.  `main` itself must refuse
the CPU, and the script must fail when it is copied out of the repo.
"""
import os
import shutil
import subprocess
import sys
import textwrap

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def _env():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    return env


def test_train_phase_rehearsal(tmp_path):
    rec = chip_smoke.train_phase(ckpt_dir=str(tmp_path), reduced=True,
                                 batch=2, seq=64)
    checks = chip_smoke.train_verdict(rec, on_tpu=False)
    assert all(checks.values()), {k: v for k, v in checks.items() if not v}
    assert rec["restore"]["step"] == 5
    assert rec["snapshots"] == [1, 3, 5]
    assert rec["children"] >= 4                    # one SMP per member
    assert rec["shm_bytes"] > 0


SHARDED = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, {root!r})
    import chip_smoke as cs
    if __name__ == "__main__":
        rec = cs.sharded_phase(ckpt_dir={ckpt!r}, reduced=True, batch=4,
                               seq=8, options={{"device_encode": "on"}})
        checks = cs.sharded_verdict(rec, on_tpu=False)
        assert rec["device_encode"] is True
        assert all(checks.values()), checks
        print("SHARDED_OK", len(rec["devices"]))
""")


def test_sharded_phase_rehearsal_on_4_cpu_devices(tmp_path):
    """The --chips 4 phase on a forced 2x2 CPU mesh, device encode on
    (interpreted): EP equals GSPMD, and every device's slice comes back
    byte-exact through the sharded restore."""
    script = tmp_path / "sharded.py"      # spawn re-imports __main__
    script.write_text(SHARDED.format(root=ROOT, ckpt=str(tmp_path / "ck")))
    out = subprocess.run([sys.executable, str(script)], env=_env(),
                         capture_output=True, text=True, timeout=600)
    assert "SHARDED_OK 4" in out.stdout, out.stderr[-3000:]


def test_main_refuses_the_cpu(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_script_alone_fails_without_result(tmp_path):
    """Copied away from the repo, the script exits nonzero and prints no
    result line."""
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = _env()
    env.pop("PYTHONPATH")
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
