"""CPU rehearsals of the benchmark's command at the program's smoke-test
sizes (`--reduced`): the result line, the window loop against the
`launch/train.py`, the refusal without a chip, and shared-memory hygiene."""
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

import harness
import run

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
KEYS = ("correct", "attempted", "failed", "metrics", "device")


def rehearse(capsys, workload, *extra, seed=5, seconds=1.0):
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--reduced", *extra])
    out, err = capsys.readouterr()
    return rc, out.strip().splitlines(), err


def last_line(lines):
    return json.loads(lines[-1])


@pytest.mark.parametrize("workload, metrics", [
    ("opt125m.save_every_step", {"train_tokens_per_s", "setup_s"}),
    # a cell BENCHMARK.json does not list: its checks, no metrics
    ("opt125m.node_failure", set()),
    ("opt350m.no_saving", {"train_tokens_per_s", "setup_s"}),
])
def test_last_line_has_the_contract_keys(capsys, workload, metrics):
    rc, lines, err = rehearse(capsys, workload, "--trace", "0",
                              seconds=1.5 if "failure" in workload else 1.0)
    assert rc == 0, err[-3000:]
    res = last_line(lines)
    assert list(res)[:5] == list(KEYS) and list(res)[-1] == "checks"
    assert res["correct"] is True, err[-3000:]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == metrics
    assert res["device"]["platform"] == "cpu"
    assert any(line.startswith("[bench] compiles_in_window = ")
               for line in lines[:-1])
    for name, c in res["checks"].items():
        assert f"[check] {name} = " in err


def test_traced_run_reads_the_per_layer_counters(capsys):
    rc, lines, err = rehearse(capsys, "opt125m.save_every_step",
                              "--trace", "1")
    assert rc == 0, err[-3000:]
    res = last_line(lines)
    assert res["correct"] is True
    # no device plane and no peak for the CPU: every per-layer reader finds
    # nothing to read, and the line leaves its metric out (none reads 0)
    assert res["metrics"] == {}
    assert "busy_s" not in res["device"] and "breakdown" not in res
    assert "[info] gc_in_window = " in err


@pytest.mark.parametrize("workload, kernels", [
    ("opt350m.save_every_step", ("crc32_chunks", "xor_reduce")),
    ("opt350m.no_saving", ()),
])
def test_kernels_come_from_the_metric_readers(workload, kernels):
    """The trace reduction keeps the kernels the cell's per-layer readers
    name, so a reader of a new kernel is one new file."""
    entries = harness.benchmark_entries(workload)
    assert run.kernels_of(run.readers(entries, "per_layer")) == kernels


def test_program_config_holds_the_file_to_the_program():
    conf = harness.load_json("configs", "opt-125m.json")
    cfg, _ = harness.program_config(conf)
    assert (cfg.num_layers, cfg.d_model, cfg.family) == (12, 768, "dense")
    moe = json.loads(json.dumps(conf))
    moe["program"]["architecture"]["num_experts"] = 8
    with pytest.raises(ValueError, match="num_experts"):
        harness.program_config(moe)


def test_a_fault_may_run_on_the_chip():
    args = run.parse(["--workload", "opt125m.save_every_step", "--seed",
                      "2147483999", "--seconds", "5", "--fault", "control"])
    assert args.fault == "control" and not args.reduced


def test_window_loop_gives_the_train_entry_points_losses(capsys, monkeypatch,
                                                     tmp_path):
    """Seed 0, the same three steps: the benchmark's loop and
    `repro.launch.train.main` train the same numbers.  The benchmark makes
    the weights in one jitted call where the program makes them op by op,
    so a few weights differ in their last bit (XLA folds the init scale
    into the normal's own multiply): 1e-6 relative, where a different
    batch or a skipped step moves the loss by 1e-2.  Both steps are
    compiled here: `train.main`'s persistent cache is pointed at an empty
    directory that it does not open."""
    from repro.launch import train
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    rc, lines, err = rehearse(capsys, "opt350m.no_saving", "--trace", "0",
                              seed=0)
    assert rc == 0, err[-3000:]
    m = re.search(r"program losses (\[[^\]]*\])", err)
    bench_losses = json.loads(m.group(1))
    got = []

    def observe(event, **kw):
        if event == "step":
            got.append(kw["loss"])
    assert train.main(["--arch", "opt-350m", "--reduced", "--steps", "3",
                       "--batch", "2", "--seq", "64", "--backend", "null"],
                      observe=observe) == 0
    capsys.readouterr()
    np.testing.assert_allclose(got, bench_losses, rtol=1e-6)
    assert got[0] == bench_losses[0]


def _cli(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=240)


def test_refuses_to_run_without_a_tpu():
    p = _cli(ROOT, "--workload", "opt125m.save_every_step", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
    assert "needs a TPU" in p.stderr


def test_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path, "--workload", "opt125m.save_every_step", "--seed",
             "1", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_a_run_that_fails_mid_window_leaves_no_segments(capsys):
    rc, lines, err = rehearse(capsys, "opt125m.save_every_step",
                              "--trace", "0", "--fault", "crash")
    assert rc != 0
    assert not any(line.startswith("{") for line in lines)
    (run_id,) = re.findall(r"\[bench\] run id (\w+)", "\n".join(lines))
    assert "crash planted in the window" in err
    left = [n for n in os.listdir("/dev/shm") if n.startswith(
        f"reft-{run_id}-")]
    assert left == []
