"""The check of the check: a run whose timed path is broken underneath
(`run.py --fault`, CPU rehearsal at the program's smoke sizes) must come
out `correct: false`, on the number that the fault moves.

* control       the reference in fp8 put in the program's place;
* stale_state   a step that returns its state unchanged;
* half_batch    half of each batch left out, the mean over the rest;
* flip_snapshot one byte of a published snapshot altered in shared memory;
* flip_restore  one byte of a restored state altered where it is produced.

One chip, so there is no exchange between chips to leave out."""
import json

import pytest

import run

TRAIN = ("loss_gap", "grad_gap", "update_gap")


@pytest.mark.parametrize("workload, fault, fails", [
    ("opt125m.save_every_step", "control", TRAIN),
    ("opt125m.save_every_step", "stale_state", TRAIN),
    ("opt125m.save_every_step", "half_batch", TRAIN),
    ("opt125m.save_every_step", "flip_snapshot", ("snapshot_mismatch_bytes",)),
    ("opt125m.node_failure", "flip_restore", ("restore_mismatch_bytes",)),
])
def test_a_broken_timed_path_is_not_correct(capsys, workload, fault, fails):
    rc = run.main(["--workload", workload, "--seed", "9", "--seconds",
                   "1.5", "--trace", "0", "--reduced", "--fault", fault])
    out, err = capsys.readouterr()
    assert rc == 0, err[-3000:]
    res = json.loads(out.strip().splitlines()[-1])
    assert res["correct"] is False, err[-3000:]
    over = [k for k, c in res["checks"].items()
            if c["value"] is None or c["value"] > c["limit"]]
    assert set(over) & set(fails), res["checks"]
