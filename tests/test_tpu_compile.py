"""The saving path's device programs and the train step's attention
compile for a TPU v5e.

Compiled here for a described (not attached) v5e chip: what Mosaic or
XLA:TPU would refuse on the chip fails here, at no chip time.  Nothing
runs, so these tests say nothing about results or speed — the
interpret-mode equality tests in test_kernels.py cover results.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and every test worker
imports every test file.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

BUCKET = 4 << 20                   # the default ReftConfig.bucket_bytes


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _is_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("k,nbytes,want_crc", [
    (1, BUCKET, True),             # own-data bucket, default size
    (1, 12345, True),              # small odd tail bucket
    (3, BUCKET, False),            # parity bucket (no checksum)
    (3, BUCKET, True),             # parity digest of a delta flight
])
def test_encode_bucket_compiles_for_v5e(one_chip, k, nbytes, want_crc):
    from repro.kernels.stage import LANE_BYTES, encode_bucket
    n_lanes = -(-nbytes // LANE_BYTES) * (LANE_BYTES // 4)
    blocks = _spec((k, n_lanes), jnp.uint32, one_chip)
    compiled = jax.jit(lambda b: encode_bucket(
        b, nbytes=nbytes, want_crc=want_crc, interpret=False)) \
        .lower(blocks).compile()
    assert _is_kernel(compiled)


def test_xor_reduce_compiles_for_v5e(one_chip):
    from repro.kernels.xor_parity import xor_reduce
    blocks = _spec((3, BUCKET // 4), jnp.uint32, one_chip)
    compiled = jax.jit(lambda b: xor_reduce(b, interpret=False)) \
        .lower(blocks).compile()
    assert _is_kernel(compiled)


@pytest.mark.parametrize("shape,dtype", [
    ((50272, 768), jnp.float32),   # opt-125m's largest leaf (Adam moment)
    ((12, 768, 3072), jnp.bfloat16),   # a stacked bf16 weight
    ((768,), jnp.bfloat16),        # a vector leaf
])
def test_leaf_gather_transient_within_twice_the_bucket(one_chip, shape,
                                                       dtype):
    """The device byte gather never materialises a padded narrow-dtype
    copy of the leaf: its transient stays within 2x the bytes it
    gathers (and so within 2x the leaf)."""
    from repro.kernels.stage import _place
    out = _spec((BUCKET // 4,), jnp.uint32, one_chip)
    leaf = _spec(shape, dtype, one_chip)
    i32 = _spec((), jnp.int32, one_chip)
    mem = _place.lower(out, leaf, i32, i32, i32, i32).compile() \
        .memory_analysis()
    leaf_bytes = int(np.prod(shape)) * jnp.dtype(dtype).itemsize
    assert mem.temp_size_in_bytes <= 2 * min(BUCKET, leaf_bytes)


@pytest.mark.parametrize("shape", [
    (12, 1024, 4096),              # opt-350m's stacked MLP weight
    (50272, 768),                  # opt-125m's embedding
    (1024, 50272),                 # opt-350m's head: 25,136 words a row
    (1, 8, 2304, 896),             # mellum2's held experts: 448 words
])
def test_leaf_gather_packs_16bit_with_kernel_for_v5e(one_chip, shape):
    """A bf16 leaf's gather packs words in the Pallas kernel: the program
    has a `tpu_custom_call` and no element `gather`."""
    from repro.kernels.stage import _place
    out = _spec((BUCKET // 4,), jnp.uint32, one_chip)
    leaf = _spec(shape, jnp.bfloat16, one_chip)
    i32 = _spec((), jnp.int32, one_chip)
    compiled = _place.lower(out, leaf, i32, i32, i32, i32).compile()
    assert _is_kernel(compiled)
    assert " gather(" not in compiled.as_text()


# ------------------------------------------------ train-step attention
def _attention_grad(cfg, S, unroll=False, band=None, window=None):
    """d(sum of one attention layer's output)/d(params, x) under
    jax.checkpoint, as the train step's remat scan body takes it.
    window: as the layer stack gives it -- None (full) or a Python int,
    or a traced int32 where the local:global pattern does not tile the
    depth."""
    from repro.models.attention import attention
    positions = jnp.arange(S, dtype=jnp.int32)

    def loss(p, x):
        o, _ = attention(p, cfg, x, window=window, positions=positions,
                         band=band, unroll=unroll)
        return jnp.sum(o.astype(jnp.float32))
    return jax.grad(jax.checkpoint(loss), argnums=(0, 1))


def _attention_args(cfg, B, S, sharding=None):
    from repro.models.attention import init_attn
    p = jax.eval_shape(lambda: init_attn(jax.random.PRNGKey(0), cfg))
    p = jax.tree.map(lambda t: _spec(t.shape, t.dtype, sharding), p)
    return p, _spec((B, S, cfg.d_model), jnp.bfloat16, sharding)


def test_causal_attention_lowers_to_kernel_for_v5e(one_chip):
    """opt-350m's layer (B 4, S 2048, H 16, hd 64): the splash kernel,
    forward and backward, no block loop, and temp far under the loops'
    (4.86 GB for the scanned form)."""
    from repro.configs import get_config
    cfg = get_config("opt-350m")
    compiled = jax.jit(_attention_grad(cfg, 2048)) \
        .lower(*_attention_args(cfg, 4, 2048, one_chip)).compile()
    text = compiled.as_text()
    assert _is_kernel(compiled)
    assert "while" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("case", ["sliding_window", "banded", "unroll",
                                  "non_causal"])
def test_other_attention_keeps_the_loops_for_v5e(one_chip, case):
    """A window traced through the scan (a local:global pattern that
    does not tile the depth), banded, unrolled (dry-run) and
    bidirectional attention lower to the pure-JAX loops on a TPU too."""
    import dataclasses
    from repro.configs import get_config
    cfg = get_config("opt-350m")
    band, unroll, window = None, False, None
    if case in ("sliding_window", "banded"):
        cfg = dataclasses.replace(cfg, sliding_window=512)
        band = 512 if case == "banded" else None
        window = jnp.int32(512) if case == "sliding_window" else 512
    elif case == "unroll":
        unroll = True
    else:
        cfg = dataclasses.replace(cfg, causal=False)
    lowered = jax.jit(_attention_grad(cfg, 2048, unroll=unroll, band=band,
                                      window=window)) \
        .lower(*_attention_args(cfg, 1, 2048, one_chip))
    assert "tpu_custom_call" not in lowered.as_text()


def test_causal_attention_lowers_to_the_loops_on_cpu():
    """The same call lowered for the CPU has no custom call at all."""
    from repro.configs import get_config
    cfg = get_config("opt-350m")
    text = jax.jit(_attention_grad(cfg, 2048)) \
        .lower(*_attention_args(cfg, 4, 2048)).as_text()
    assert "custom_call" not in text


# ------------------------------------------------------ whole train steps
def _step_for_v5e(one_chip, arch, B, S, **over):
    """The train step of `arch` (with `over` replaced) compiled for a
    described v5e: (HLO text, temp bytes, {kernel name: count})."""
    import collections
    import dataclasses
    import re
    from repro.configs import get_config
    from repro.optim.adam import AdamConfig
    from repro.train.steps import init_train_state, make_train_step
    cfg = dataclasses.replace(get_config(arch), **over)
    state = jax.eval_shape(lambda: init_train_state(cfg, 0).tree())
    state = jax.tree.map(lambda t: _spec(t.shape, t.dtype, one_chip), state)
    tok = _spec((B, S), jnp.int32, one_chip)
    compiled = jax.jit(make_train_step(cfg, AdamConfig(
        moments_dtype="float32"))).lower(
        state, {"tokens": tok, "labels": tok}).compile()
    text = compiled.as_text()
    kernels = collections.Counter(re.findall(
        r"%(splash_mqa_\w+?|gmm|tgmm)(?:\.\d+)? = ", text))
    return text, compiled.memory_analysis().temp_size_in_bytes, kernels


def _whiles_under(text, scope):
    import re
    return [n for n in re.findall(r'%while\S* = [^\n]*op_name="([^"]*)"',
                                  text) if f"/{scope}/" in n]


def test_mellum2_step_lowers_to_kernels_for_v5e(one_chip):
    """Mellum2's cut (4 layers: 3 windowed + 1 full; 8 of 64 experts held;
    B 2, S 8192) at its published widths: every layer's attention is the
    splash kernel (forward twice under remat, dq, dkv), with no loop under
    `attention`; the experts are megablox's grouped products (3 forward,
    3 recomputed, 3 input-gradient `gmm` and 3 weight-gradient `tgmm` a
    layer).  Temp 2.88 GB when written; the cell holds about 3.3 copies of
    W (3.40 GB) beside it on a 16 GiB chip, so over 4.5 GB it would not
    fit."""
    text, temp, kernels = _step_for_v5e(
        one_chip, "mellum2-12b-a2.5b", 2, 8192, num_layers=4,
        vocab_size=12288, experts_held=8)
    assert kernels == {"splash_mqa_fwd_residuals": 8,
                       "splash_mqa_dq_no_residuals": 4,
                       "splash_mqa_dkv_no_residuals": 4,
                       "gmm": 36, "tgmm": 12}
    assert _whiles_under(text, "attention") == []
    assert temp < 4.5e9


def test_opt_step_lowering_kept_for_v5e(one_chip):
    """opt-350m's cut (12 layers, B 4, S 2048) lowers as before the
    windowed and sparse-expert paths came: one scanned layer with the
    splash kernel (forward twice, dq, dkv), nothing of the MoE layer, and
    the same temp as then (2,481,453,056 B)."""
    text, temp, kernels = _step_for_v5e(one_chip, "opt-350m", 4, 2048,
                                        num_layers=12)
    assert kernels == {"splash_mqa_fwd_residuals": 2,
                       "splash_mqa_dq_no_residuals": 1,
                       "splash_mqa_dkv_no_residuals": 1}
    assert "moe." not in text
    assert _whiles_under(text, "attention") == []
    assert temp == 2_481_453_056
