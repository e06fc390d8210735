"""The saving path's device programs compile for a TPU v5e.

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
