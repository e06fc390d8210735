"""End-to-end behaviour: the training driver with mid-run fault injection
(deliverable b's driver, exercised as a test)."""
import sys

import pytest


def test_train_driver_with_failures(tmp_path):
    from repro.launch.train import main
    rc = main(["--arch", "opt-125m", "--reduced", "--steps", "16",
               "--batch", "2", "--seq", "64", "--sg-size", "4",
               "--snapshot-every", "2", "--ckpt-dir", str(tmp_path),
               "--inject", "6:software", "--inject", "12:node"])
    assert rc == 0


def test_train_exits_nonzero_with_a_degraded_member(tmp_path, capsys):
    """Training rides through a lost saving sidecar, but `main` does not
    report success: it names the member and its first flight error,
    and exits 1."""
    from repro.launch.train import main

    def observe(event, sess, **kw):
        if event == "step" and kw["step"] == 2:
            sess.checkpointer.group.engines[2].smp.kill()

    rc = main(["--arch", "opt-125m", "--reduced", "--steps", "6",
               "--batch", "2", "--seq", "64", "--sg-size", "4",
               "--snapshot-every", "2", "--ckpt-every", "100",
               "--ckpt-dir", str(tmp_path)], observe=observe)
    err = capsys.readouterr().err
    assert rc == 1
    assert "[degraded] member 2" in err


def test_train_driver_no_reft(tmp_path):
    from repro.launch.train import main
    rc = main(["--arch", "opt-125m", "--reduced", "--steps", "6",
               "--batch", "2", "--seq", "64", "--no-reft"])
    assert rc == 0


def test_quickstart_example_runs():
    sys.path.insert(0, "examples")
    try:
        import quickstart
        quickstart.main()
    finally:
        sys.path.pop(0)
