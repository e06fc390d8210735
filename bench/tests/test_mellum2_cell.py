"""The mellum2 cell's files at small sizes on the CPU: the configuration
file holds the program to its switches, the reference follows the
program's first three steps, the fp8 control and the half-batch fault do
not, and the operation counts.  (`run.py --reduced` cannot run this cell:
the reduced program has 4 experts where the file states 64.)"""
import dataclasses
import json

import pytest

import correct
import harness
import readings

WORKLOAD = "mellum2.save_every_step"


def small():
    """A small configuration file and the program's config to match:
    2 layers (a 16-key window, then full), 4 experts of which 2 held."""
    conf = harness.load_json("configs", "mellum2-12b-a2.5b.json")
    conf.update(num_hidden_layers=2, hidden_size=128, num_attention_heads=4,
                num_key_value_heads=2, head_dim=32,
                moe_intermediate_size=256, ffn_dim=256, vocab_size=256,
                num_experts=4, num_experts_per_tok=2, num_experts_held=2,
                sliding_window=16, param_dtype="float32",
                compute_dtype="float32",
                layer_types=["sliding_attention", "full_attention"])
    from repro.configs import get_config
    cfg = dataclasses.replace(
        get_config("mellum2-12b-a2.5b"), num_layers=2, d_model=128,
        num_heads=4, num_kv_heads=2, head_dim=32, d_ff=256, vocab_size=256,
        num_experts=4, experts_per_token=2, experts_held=2,
        sliding_window=16, global_every=2, dtype="float32",
        param_dtype="float32", remat=False)
    return conf, cfg


def test_the_file_holds_the_program_to_its_switches():
    conf = harness.load_json("configs", "mellum2-12b-a2.5b.json")
    cfg, _ = harness.program_config(conf)
    assert (cfg.num_layers, cfg.num_experts, cfg.num_experts_held,
            cfg.vocab_size) == (4, 64, 8, 12288)
    bad = json.loads(json.dumps(conf))
    bad["program"]["architecture"]["capacity_factor"] = 1.25
    with pytest.raises(ValueError, match="capacity_factor"):
        harness.program_config(bad)


@pytest.fixture(scope="module")
def gaps():
    conf, cfg = small()
    ref = harness.load_module("reference", conf["reference"])
    stepper = readings.program_stepper(cfg, ref, harness.adam_config(conf))
    batches = [ref.host_batch(conf, 2 ** 31 + 7, s, 2, 64) for s in range(3)]
    f32 = ref.follow(conf, 2 ** 31 + 7, batches)
    opt = conf["optimizer"]

    def as_prog(r):
        return {"loss": r["loss"],
                "mu_norms": [g * (1 - opt["b1"]) for g in r["grad_norms"]],
                "change_norms": r["change_norms"]}
    prog = readings.program_numbers(conf, ref, 2 ** 31 + 7, batches, stepper)
    fp8 = ref.follow(conf, 2 ** 31 + 7, batches, fp8=True)
    half = ref.follow(conf, 2 ** 31 + 7, [(t[:1], lab[:1])
                                          for t, lab in batches])
    return {name: correct.train_gaps(r, f32, opt) for name, r in (
        ("program", prog), ("fp8", as_prog(fp8)), ("half", as_prog(half)))}


def test_reference_follows_the_program(gaps):
    """In float32 the program and the reference differ by round-off."""
    g = gaps["program"]
    assert g["loss_gap"] < 1e-5 and g["grad_gap"] < 1e-4 \
        and g["update_gap"] < 1e-3, g


@pytest.mark.parametrize("fault", ["fp8", "half"])
def test_control_and_fault_stand_apart_from_the_program(gaps, fault):
    """Every gap of the fp8 control and of the half-batch fault is over a
    thousand times the float32 program's.  (The cell's limits are set for
    the bf16 program on the chip, between its readings and the control's,
    which these small float32 sizes do not reproduce.)"""
    g, p = gaps[fault], gaps["program"]
    for k in ("loss_gap", "grad_gap", "update_gap"):
        assert g[k] > 1e3 * p[k], (k, g, p)


def test_half_batch_fault_fails_the_cells_limits(gaps):
    limits = harness.load_json("workloads", WORKLOAD + ".json")["limits"]
    g = gaps["half"]
    assert any(g[k] > v for k, v in limits.items() if k in g), g


def test_train_flops_at_the_cells_sizes():
    """19.24 TFLOP a step at B 2 x S 8192: 6 x 138.6 M active weights a
    token (attention, router, one expert's share, head) and 57.15 M
    query-key pairs a sequence (3 windowed layers of 7,864,832, one full
    of 33,558,528)."""
    conf = harness.load_json("configs", "mellum2-12b-a2.5b.json")
    ref = harness.load_module("reference", conf["reference"])
    pairs = 3 * (1024 * 1025 // 2 + 7168 * 1024) + 8192 * 8193 // 2
    active = 4 * (21_233_664 + 147_456 + 3 * 2304 * 896) + 2304 * 12288
    assert ref.train_flops(conf, 2, 8192) == \
        6 * active * 16384 + 12 * 2 * 32 * 128 * pairs
