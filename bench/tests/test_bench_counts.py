"""The yardstick's arithmetic: step FLOPs against hand counts, and the
peak table."""
import json
import os

import pytest

import counts

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


def conf(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name, batch, matmul, attention", [
    # opt-125m: 12 x (4 x 768^2 + 3 x 768 x 3072) + 768 x 50272
    ("opt-125m", 8, 12 * (4 * 768 ** 2 + 3 * 768 * 3072) + 768 * 50272,
     # 3 (fwd + bwd) x 2 products x 2 ops x B x L x H x hd x S(S+1)/2
     3 * 2 * 2 * 8 * 12 * 12 * 64 * (2048 * 2049 // 2)),
    # opt-350m cut to 12 layers: 12 x (4 x 1024^2 + 3 x 1024 x 4096) + head
    ("opt-350m", 4, 12 * (4 * 1024 ** 2 + 3 * 1024 * 4096) + 1024 * 50272,
     3 * 2 * 2 * 4 * 12 * 16 * 64 * (2048 * 2049 // 2)),
])
def test_train_flops_match_hand_counts(name, batch, matmul, attention):
    c = conf(name)
    assert counts.matmul_params(c) == matmul
    got = counts.train_flops(c, batch, 2048)
    assert got == 6 * matmul * batch * 2048 + attention
    # the orders of magnitude the cells are sized by
    assert 1.2e13 < got < 1.8e13


def test_matmul_params_of_the_cut_opt350m():
    assert counts.matmul_params(conf("opt-350m")) == 252_805_120


def test_peaks_known_and_unknown_kinds():
    p = counts.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")
