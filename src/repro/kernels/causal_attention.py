"""Causal full-sequence attention on the TPU: JAX's Pallas splash kernel.

The train step's attention for causal layers at long S, full or with a
sliding window fixed at trace time (`models.attention.attention` selects
it for a TPU lowering).  Forward,
dq and dkv are three Pallas calls, named `splash_mqa_fwd_residuals` (the
forward under autodiff), `splash_mqa_dq_no_residuals` and
`splash_mqa_dkv_no_residuals` in a trace: each (block_q, block_kv) tile
of scores stays in VMEM, and tiles wholly masked (above the diagonal, or
past a window) are skipped.  Same mathematics as
`models.flash.flash_attention`, which is its reference: bf16 operands, f32
accumulation, softmax over keys at or before the query and, with a window
W, less than W positions before it.

GQA layout: the G query heads of one KV head are the kernel's heads in its
MQA form, vmapped over batch and KV heads.
"""
from __future__ import annotations

import functools

import jax
from jax.experimental.pallas.ops.tpu import splash_attention as splash

# Tile edges, from a v5e sweep of 256 / 512 / 1024 at S 2048, hd 64
# (PERF.md): 512 for the forward, 1024 for the dq and dkv kernels.
FWD_BLOCK, BWD_BLOCK = 512, 1024


def _edge(seq_len: int, target: int) -> int:
    """The largest tile edge up to `target` that divides S and is a
    multiple of the 128-lane vreg; 0 if there is none."""
    b = target
    while b >= 128 and seq_len % b:
        b //= 2
    return b if b >= 128 else 0


def block_sizes(seq_len: int):
    """The kernel's tiles at `seq_len`, or None where none fits."""
    fwd, bwd = _edge(seq_len, FWD_BLOCK), _edge(seq_len, BWD_BLOCK)
    if not (fwd and bwd):
        return None
    return splash.BlockSizes(
        block_q=fwd, block_kv=fwd, block_kv_compute=fwd,
        block_q_dkv=bwd, block_kv_dkv=bwd, block_kv_dkv_compute=bwd,
        block_q_dq=bwd, block_kv_dq=bwd)


@functools.lru_cache(maxsize=8)
def _kernel(seq_len: int, heads: int, window, interpret: bool):
    """The mask tables and kernel for one shape and window, built once
    (not per trace); under compile-time eval so a first call inside a
    trace still caches concrete tables, not tracers."""
    shape = (seq_len, seq_len)
    one = (splash.CausalMask(shape) if window is None else
           splash.LocalMask(shape, window_size=(window - 1, 0), offset=0))
    mask = splash.MultiHeadMask([one] * heads)
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mqa_single_device(
            mask, block_sizes=block_sizes(seq_len), interpret=interpret)


def causal_attention(q, k, v, *, window=None, interpret: bool = False):
    """q: (B,S,KV,G,hd), k/v: (B,S,KV,hd) -> (B,S,KV,G,hd) in q.dtype.

    window: None (full causal) or a Python int W (keys q - W < k <= q).
    S must have `block_sizes(S)`.  `interpret=True` runs the kernel in the
    Pallas interpreter (the CPU tests)."""
    B, S, KV, G, hd = q.shape
    if block_sizes(S) is None:
        raise ValueError(f"no tile edge divides S={S}")
    kernel = _kernel(S, G, window, interpret)
    qh = (q * hd ** -0.5).transpose(0, 2, 3, 1, 4)        # (B,KV,G,S,hd)
    kh = k.transpose(0, 2, 1, 3)                           # (B,KV,S,hd)
    vh = v.transpose(0, 2, 1, 3)
    o = jax.vmap(jax.vmap(kernel))(qh, kh, vh)             # (B,KV,G,S,hd)
    return o.transpose(0, 3, 1, 2, 4).astype(q.dtype)
