"""Public jit'd wrappers around the Pallas kernels.

These are the entry points used by the rest of the system.  With
`interpret=None` each kernel follows the placement of its input
(`repro.kernels.placement`): compiled to Mosaic for an accelerator-
resident array, interpreted for a CPU-resident one.  Each has a pure-jnp
oracle in ref.py.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels.placement import runs_interpreted
from repro.kernels.ssd_scan import ssd_scan as _ssd_scan_kernel
from repro.kernels.swa_attention import swa_flash as _swa_flash_kernel
from repro.kernels.xor_parity import xor_reduce as _xor_reduce_kernel


def xor_parity_encode(blocks, *, interpret: bool = None):
    """XOR parity of k byte blocks. blocks: (k, nbytes) uint8 -> (nbytes,).

    Pads to 4-byte lanes (uint32) for the TPU kernel.  `interpret=None`
    follows the placement of `blocks`.
    """
    if interpret is None:
        interpret = runs_interpreted(blocks)
    blocks = jnp.asarray(blocks)
    assert blocks.dtype == jnp.uint8 and blocks.ndim == 2
    k, n = blocks.shape
    pad = (-n) % 512                       # 128 lanes x 4 bytes
    if pad:
        blocks = jnp.pad(blocks, ((0, 0), (0, pad)))
    lanes = jax.lax.bitcast_convert_type(
        blocks.reshape(k, -1, 4), jnp.uint32).reshape(k, -1)
    out = _xor_reduce_kernel(lanes, interpret=interpret)
    out8 = jax.lax.bitcast_convert_type(
        out.reshape(-1, 1), jnp.uint8).reshape(-1)
    return out8[:n]


def xor_parity_decode(survivors, parity, *, interpret: bool = None):
    """Reconstruct the missing block: XOR(survivors..., parity)."""
    stack = jnp.concatenate(
        [jnp.asarray(parity)[None], jnp.asarray(survivors)], axis=0)
    return xor_parity_encode(stack, interpret=interpret)


def ssd_scan(u, a, Bm, Cm, h0=None, *, chunk: int = 128,
             interpret: bool = None):
    """Chunked SSD (Mamba2). Same contract as models.ssm.ssd_chunked."""
    if interpret is None:
        interpret = runs_interpreted(u)
    return _ssd_scan_kernel(u, a, Bm, Cm, h0, chunk=chunk,
                            interpret=interpret)


def swa_attention(q, k, v, *, window=None, causal: bool = True,
                  block_q: int = 128, block_k: int = 128,
                  interpret: bool = None):
    """Banded flash attention; window is a *static* int (None = full)."""
    if interpret is None:
        interpret = runs_interpreted(q)
    return _swa_flash_kernel(q, k, v, window=window, causal=causal,
                             block_q=block_q, block_k=block_k,
                             interpret=interpret)
