"""RAIM5 XOR parity as a Pallas TPU kernel.

The paper computes parity "byte-wise on the CPU"; the device encode path
folds parity *on the accelerator before the d2h copy*, so the host
receives shard + parity in one stream and the XOR rides otherwise idle
VPU cycles.  Lanes are uint32 (the TPU's native integer width) laid out
lane-dense as (rows, 128) blocks.

encode: parity[t] = XOR_i blocks[i, t]      blocks: (k, n) uint32
decode: missing   = XOR(survivors, parity)  == encode on (k, n) stacked

`interpret=None` (the default) follows the input's placement
(`repro.kernels.placement`): compiled for an accelerator-resident array,
interpreted for a CPU-resident one.  A lane count that does not fill whole
(8, 128) tiles is zero-padded (the XOR identity).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.placement import runs_interpreted

LANES = 128


def _xor_kernel(blocks_ref, out_ref):
    k = blocks_ref.shape[0]
    acc = blocks_ref[0]
    for i in range(1, k):                    # k is static and small (SG size)
        acc = jax.lax.bitwise_xor(acc, blocks_ref[i])
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("block_elems", "interpret"))
def _xor_reduce(blocks, *, block_elems: int, interpret: bool):
    k, n = blocks.shape
    rows = -(-n // LANES)
    # block height: a multiple of 8 sublanes, never taller than the data
    br = max(8, min(block_elems // LANES // 8 * 8, -(-rows // 8) * 8))
    rows_pad = -(-rows // br) * br
    n_pad = rows_pad * LANES
    if n_pad != n:
        blocks = jnp.pad(blocks, ((0, 0), (0, n_pad - n)))
    out = pl.pallas_call(
        _xor_kernel,
        grid=(rows_pad // br,),
        in_specs=[pl.BlockSpec((k, br, LANES), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((br, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_pad, LANES), jnp.uint32),
        interpret=interpret,
        name="xor_reduce",
    )(blocks.reshape(k, rows_pad, LANES)).reshape(-1)
    return out[:n] if n_pad != n else out


def xor_reduce(blocks: jax.Array, *, block_elems: int = 64 * 1024,
               interpret: bool = None) -> jax.Array:
    """XOR-reduce along axis 0. blocks: (k, n) uint32 -> (n,) uint32."""
    assert blocks.dtype == jnp.uint32 and blocks.ndim == 2
    if interpret is None:
        interpret = runs_interpreted(blocks)
    return _xor_reduce(blocks, block_elems=block_elems, interpret=interpret)
