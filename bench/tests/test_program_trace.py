"""The program's spans and scopes in a trace (`program_trace`): the
reduction on small traces with known answers, the recorded chip excerpt
(no program spans) reduced exactly as `trace_reduce` reduces it, and a
traced CPU rehearsal of a save cell."""
import copy
import os

import pytest

import harness
import program_trace
import run
import trace_reduce
from test_bench_trace_reduce import DATA, K, hand_trace

READERS = ("saving_dispatch_idle_share", "hasc_staged_bytes_per_s",
           "attention_busy_share")
us = 1000


def program_hand_trace():
    """`hand_trace` (window 0..100 us; idle 0-10, 35-50, 55-60, 64-95)
    with program spans on four lines (line 0 holds the window) and the
    train step's scopes on its device ops."""
    t = hand_trace()
    t["main_line"] = 0
    t["program"] = [
        ("repro.hasc.launch", 40 * us, 5 * us, 0, {}),      # main line
        ("repro.hasc.l1.dispatch", 35 * us, 15 * us, 1, {}),
        ("repro.hasc.l1.dispatch", 41 * us, 4 * us, 3, {}),
        ("repro.hasc.l1.d2h", 38 * us, 10 * us, 2, {}),
        ("repro.hasc.l1.dispatch", 60 * us, 10 * us, 1, {}),
        ("repro.hasc.l2.send", -5 * us, 10 * us, 2, {"bytes": 7}),
        ("repro.hasc.l2.send", 75 * us, 10 * us, 2, {"bytes": 100}),
        ("repro.hasc.l2.send", 95 * us, 15 * us, 2, {"bytes": 50}),
    ]
    t["scopes"] = {"/device:TPU:0": [
        ("attention", 10 * us, 20 * us),
        ("attention", 12 * us, 8 * us),           # nested in the first
        ("mlp", 25 * us, 10 * us),
        ("optimizer", 95 * us, 10 * us)]}         # cut at 100
    return t


def test_program_spans_scopes_and_gap_names():
    r = program_trace.reduce(program_hand_trace(), K)
    base = trace_reduce.reduce(hand_trace(), K)
    for key in base:
        if key != "gaps":
            assert r[key] == base[key], key
    sp = r["program_spans"]
    assert sp["repro.hasc.l1.dispatch"] == [pytest.approx(29e-6), 3]
    assert sp["repro.hasc.l2.send"] == [pytest.approx(20e-6), 3]
    assert sp["repro.hasc.launch"] == [pytest.approx(5e-6), 1]
    assert r["staged_bytes"] == 107         # the send ending at 110 is out
    # idle under a dispatch: 35-50 and 64-70
    assert r["dispatch_idle_s"] == pytest.approx(21e-6)
    assert r["scope_busy"] == {"attention": pytest.approx(20e-6),
                               "mlp": pytest.approx(10e-6),
                               "optimizer": pytest.approx(5e-6)}
    # gaps as trace_reduce orders them: 64-95, 35-50, 0-10, 55-60
    assert [g for _, g in r["gaps"]] == [g for _, g in base["gaps"]]
    assert [n for n, _ in r["gaps"]] == [
        "bench.after_step|repro.hasc.l2.send",
        "bench.after_step|repro.hasc.l1.dispatch",
        "bench.batch", "bench.after_step"]
    # the main line's launch is not counted at 42.5 us
    assert r["gap_spans"][1] == {"repro.hasc.l1.dispatch": 2,
                                 "repro.hasc.l1.d2h": 1}
    assert r["gap_spans"][2] == r["gap_spans"][3] == {}


def test_readers_of_the_program_metrics():
    rec = {"trace": program_trace.reduce(program_hand_trace(), K)}
    got = {m: harness.load_module("metrics", m).read(rec, {})
           for m in READERS}
    assert got == {"saving_dispatch_idle_share": pytest.approx(21.0),
                   "hasc_staged_bytes_per_s": pytest.approx(
                       107 / 100e-6 / 1e9),
                   "attention_busy_share": pytest.approx(100 * 20 / 39)}
    bare = {"trace": program_trace.reduce(hand_trace(), K)}
    for rec in (bare, {"trace": {"devices": 0}}, {}):
        for m in READERS:
            assert harness.load_module("metrics", m).read(rec, {}) is None


@pytest.mark.parametrize("extra", [{}, {"program": [], "scopes": {},
                                        "main_line": None}])
def test_recorded_chip_trace_reduces_as_before(extra):
    """The v5e excerpt has no program spans: every key, number and gap
    name is `trace_reduce`'s, and its recorded answers still hold."""
    ev = trace_reduce.read_saved(os.path.join(DATA,
                                              "v5e_save_excerpt.json.gz"))
    want = ev["expected"]
    ev.update(extra)
    r = program_trace.reduce(copy.deepcopy(ev), K)
    assert r == trace_reduce.reduce(ev, K)
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-4)
    for k in K:
        assert r["kernels"][k]["calls"] == want["calls"][k]
    assert r["gaps"][0][0] == want["longest_gap_span"]


@pytest.mark.parametrize("op_name, scope", [
    ("jit(train_step)/jvp()/while/body/closed_call/attention/dot_general",
     "attention"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/attention/"
     "while/body/exp", "attention"),
    ("jit(train_step)/transpose(jvp(head_loss))/dot_general", "head_loss"),
    ("jit(train_step)/optimizer/sqrt", "optimizer"),
    ("mlp", "mlp"),
    ("state['params']['embed']", None),
    ("jit(train_step)/while/body/dynamic_slice", None),
])
def test_scope_of_an_op_name(op_name, scope):
    assert program_trace.scope_of(op_name) == scope


def test_traced_rehearsal_records_the_program_spans(capsys, monkeypatch):
    """A `--reduced --trace 1` run of a save cell with `program_trace` in
    the reduction's place: the HASC threads' spans lie on other lines than
    the window's, and each reader returns a number or None."""
    seen = {}
    load, reduce = trace_reduce.load, trace_reduce.reduce

    def load_both(path, kernels=()):
        seen["events"] = dict(load(path, kernels), **program_trace.load(path))
        return seen["events"]

    def reduce_both(events, kernels=(), top=10):
        seen["trace"] = program_trace.reduce(events, kernels, top)
        return seen["trace"]
    monkeypatch.setattr(trace_reduce, "load", load_both)
    monkeypatch.setattr(trace_reduce, "reduce", reduce_both)
    rc = run.main(["--workload", "opt125m.save_every_step", "--seed", "7",
                   "--seconds", "1.0", "--reduced", "--trace", "1"])
    out, err = capsys.readouterr()
    assert rc == 0, err[-3000:]
    ev = seen["events"]
    main = ev["main_line"]
    assert main is not None
    hasc = {(n, li) for n, _, _, li, _ in ev["program"]
            if n.startswith("repro.hasc.")}
    assert {n for n, li in hasc if li != main} >= {
        "repro.hasc.l1.dispatch", "repro.hasc.l1.d2h", "repro.hasc.l2.send"}
    assert ("repro.hasc.launch", main) in hasc
    for m in READERS:
        v = harness.load_module("metrics", m).read({"trace": seen["trace"]},
                                                   {})
        assert v is None or v >= 0
