"""The reduction from trace events to busy time, kernel time and named
idle gaps, on small traces with known answers."""
import os

import pytest

import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
K = ("crc32_chunks", "xor_reduce")


def hand_trace():
    """Window 0..100 us; ops (ns): two overlapping, one kernel each, one
    partly outside the window; host spans name the gaps."""
    us = 1000
    dev = [("%fusion.1", 10 * us, 20 * us, None),
           ("%fusion.2", 25 * us, 10 * us, None),        # overlaps fusion.1
           ("%crc32_chunks.1", 50 * us, 5 * us, [8192, 128]),
           ("%xor_reduce.3", 60 * us, 4 * us, [3, 8192, 128]),
           ("%fusion.1", 95 * us, 10 * us, None)]         # cut at 100
    mods = [("jit_train_step", 10 * us, 25 * us),
            ("jit__encode", 50 * us, 14 * us),
            ("jit_train_step", 95 * us, 10 * us)]
    host = [("bench.window", 0, 100 * us),
            ("bench.batch", 0, 10 * us),
            ("bench.step", 10 * us, 30 * us),
            ("bench.after_step", 40 * us, 60 * us)]
    return {"device": {"/device:TPU:0": dev},
            "modules": {"/device:TPU:0": mods}, "host": host}


def test_busy_ops_kernels_and_gaps():
    r = trace_reduce.reduce(hand_trace(), K)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(100e-6)
    # union: 10-35, 50-55, 60-64, 95-100 = 25 + 5 + 4 + 5
    assert r["busy_s"] == pytest.approx(39e-6)
    assert r["programs"] == [["jit_train_step", pytest.approx(30e-6)],
                             ["jit__encode", pytest.approx(14e-6)]]
    crc = r["kernels"]["crc32_chunks"]
    assert crc["calls"] == 1 and crc["seconds"] == pytest.approx(5e-6)
    assert crc["shapes"] == [[8192, 128]]
    assert r["kernels"]["xor_reduce"]["shapes"] == [[3, 8192, 128]]
    gaps = r["gaps"]
    # gaps: 0-10 (batch), 35-50 (after_step), 55-60, 64-95 (after_step)
    assert gaps[0] == ["bench.after_step", pytest.approx(31e-6)]
    assert gaps[1] == ["bench.after_step", pytest.approx(15e-6)]
    assert gaps[2] == ["bench.batch", pytest.approx(10e-6)]
    assert sum(g for _, g in gaps) == pytest.approx(100e-6 - 39e-6)


def test_two_devices_are_averaged():
    t = hand_trace()
    t["device"]["/device:TPU:1"] = [("fusion.9", 0, 100_000, None)]
    r = trace_reduce.reduce(t, K)
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx((39e-6 + 100e-6) / 2)


def test_no_device_plane_reads_nothing():
    assert trace_reduce.reduce({"device": {}, "host": []}, K) == \
        {"devices": 0}


def test_kernel_operand_shape_from_the_op_text():
    text = ("%crc32_chunks.1 = u32[8,128]{1,0:T(8,128)} custom-call("
            "u32[8192,128]{1,0:T(8,128)S(1)} %reshape.2), "
            'custom_call_target="tpu_custom_call"')
    assert trace_reduce.OPERAND.search(text).group(1) == "8192,128"


def test_recorded_chip_trace():
    """A 0.2 s excerpt of a v5e trace of `opt125m.save_every_step` (2,604
    ops as `trace_reduce.load` keeps them, five CRC kernel calls), with
    the numbers worked out once, apart from `reduce`, on a 10 ns timeline
    of the excerpt (so busy time agrees to that resolution)."""
    path = os.path.join(DATA, "v5e_save_excerpt.json.gz")
    ev = trace_reduce.read_saved(path)
    r = trace_reduce.reduce(ev, K)
    want = ev["expected"]
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(want["window_s"])
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-4)
    for k in K:
        assert r["kernels"][k]["calls"] == want["calls"][k]
        assert r["kernels"][k]["seconds"] == pytest.approx(
            want["kernel_s"][k])
    assert r["gaps"][0][0] == want["longest_gap_span"]
