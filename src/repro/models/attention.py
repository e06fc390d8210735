"""GQA attention with RoPE, optional qk-norm and sliding windows.

A layer's window is a Python value where the layer stack fixes it (None:
full attention; an int: a sliding window), so the layer lowers to its own
mask, kernel and RoPE kind; where it does not (a local:global pattern that
does not tile the depth), a traced int32 with the FULL_WINDOW sentinel on
global layers.  Decode attends one query against a pre-allocated KV cache.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.causal_attention import block_sizes, causal_attention
from repro.models.flash import flash_attention
from repro.models.layers import (
    FULL_WINDOW, apply_rope, dense_init, init_rms, pdtype_of, rms_norm,
    rope_angles,
)

NEG_INF = -1e30
# From this sequence length on the (S, S) score matrix is never
# materialized: on a TPU causal attention whose window is fixed at trace
# time lowers to the Pallas kernel (`kernels/causal_attention.py`),
# everything else to the pure-JAX online-softmax loops (`models/flash.py`).
FLASH_THRESHOLD = 2048


def init_attn(key, cfg):
    D, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    pd = pdtype_of(cfg)
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (D, H * hd), pd),
        "wk": dense_init(ks[1], (D, KV * hd), pd),
        "wv": dense_init(ks[2], (D, KV * hd), pd),
        "wo": dense_init(ks[3], (H * hd, D), pd),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rms(hd, pd)
        p["k_norm"] = init_rms(hd, pd)
    return p


def _rope(cfg, positions, window):
    """A layer's cos/sin: YaRN on the full layers of a config that gives
    it, the default RoPE everywhere else."""
    if not cfg.yarn_factor:
        return rope_angles(positions, cfg.head_dim, cfg.rope_theta)
    if window is not None and not isinstance(window, int):
        raise ValueError(f"{cfg.name}: the RoPE kind needs a static window")
    if window is not None:
        return rope_angles(positions, cfg.head_dim, cfg.rope_theta)
    return rope_angles(positions, cfg.head_dim, cfg.rope_theta,
                       yarn_factor=cfg.yarn_factor,
                       original_max=cfg.yarn_original_max)


def _width(window):
    """The window as a number for masks (FULL_WINDOW for None)."""
    return FULL_WINDOW if window is None else window


def _project_qkv(p, cfg, x, positions, window):
    B, S, _ = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(B, S, H, hd)
    k = (x @ p["wk"]).reshape(B, S, KV, hd)
    v = (x @ p["wv"]).reshape(B, S, KV, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    cos, sin = _rope(cfg, positions, window)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    return q, k, v


def _gqa_scores(q, k, cfg):
    """q: (B,Sq,H,hd), k: (B,Sk,KV,hd) -> (B,KV,G,Sq,Sk) fp32."""
    B, Sq, H, hd = q.shape
    KV = cfg.num_kv_heads
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg, k,
                   preferred_element_type=jnp.float32)
    return s * (hd ** -0.5)


def _mix(scores, v, cfg):
    """scores: (B,KV,G,Sq,Sk) fp32, v: (B,Sk,KV,hd) -> (B,Sq,H*hd)."""
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    o = jnp.einsum("bkgqs,bskh->bqkgh", w, v)
    B, Sq = o.shape[0], o.shape[1]
    return o.reshape(B, Sq, cfg.num_heads * cfg.head_dim)


@jax.named_scope("attention")
def attention(p, cfg, x, *, window, positions, band=None, unroll=False):
    """Full-sequence attention (training / prefill).

    window: None (full), a static int, or a traced int32 scalar
    (FULL_WINDOW for global layers).
    positions: (S,) int32 (assumed contiguous from 0 for the flash path).
    band: static int window for exact banded attention (§Perf hillclimb).
    Returns (out, (k, v)) so prefill can populate the cache.
    """
    q, k, v = _project_qkv(p, cfg, x, positions, window)
    B, S = x.shape[0], x.shape[1]
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    if S >= FLASH_THRESHOLD or band is not None:
        qg = q.reshape(B, S, KV, H // KV, hd)
        # Tiles grow with S so the block grid stays <=16x16 — keeps the
        # unrolled dry-run compile tractable without changing totals.
        bq = max(512, S // 16)
        bk = max(1024, S // 16)

        def loops(qg, k, v):
            return flash_attention(qg, k, v, window=_width(window),
                                   causal=cfg.causal, band=band,
                                   unroll=unroll, block_q=bq, block_k=bk)

        if (band is None and not unroll and cfg.causal
                and (window is None or isinstance(window, int))
                and block_sizes(S) is not None):
            # Chosen per lowering platform, so a compile for a described
            # TPU gets the kernel and a CPU run the loops.
            kernel = functools.partial(causal_attention, window=window)
            o = jax.lax.platform_dependent(qg, k, v, tpu=kernel,
                                           default=loops)
        else:
            o = loops(qg, k, v)
        out = o.reshape(B, S, H * hd) @ p["wo"]
        return out, (k, v)
    qpos = positions[:, None]
    kpos = positions[None, :]
    ok = kpos - qpos < 1 if cfg.causal else jnp.ones((S, S), bool)
    w = _width(window)
    ok = ok & (qpos - kpos < w) & (kpos - qpos < w)
    scores = _gqa_scores(q, k, cfg)
    scores = jnp.where(ok[None, None, None], scores, NEG_INF)
    out = _mix(scores, v, cfg) @ p["wo"]
    return out, (k, v)


def attention_decode(p, cfg, x, cache_k, cache_v, *, window, index):
    """One-token decode. x: (B,1,D); cache_k/v: (B,Smax,KV,hd); index: scalar.

    Writes the new k/v at `index` and attends over positions <= index within
    the sliding window. Returns (out, new_k, new_v).
    """
    pos = jnp.full((1,), index, jnp.int32)
    q, k1, v1 = _project_qkv(p, cfg, x, pos, window)
    Smax = cache_k.shape[1]
    # Ring-buffer write: slot = index % Smax. When Smax covers the full
    # sequence this is a plain positional write; when the cache is
    # window-sized (window_kv_cache) old entries are overwritten.
    slot = jax.lax.rem(index, Smax)
    ck = jax.lax.dynamic_update_slice_in_dim(cache_k, k1.astype(cache_k.dtype),
                                             slot, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cache_v, v1.astype(cache_v.dtype),
                                             slot, axis=1)
    j = jnp.arange(Smax, dtype=jnp.int32)
    kpos = index - jax.lax.rem(index - j, Smax)           # true position of slot j
    ok = (kpos >= 0) & (kpos <= index) & (index - kpos < _width(window))
    scores = _gqa_scores(q, ck, cfg)                   # (B,KV,G,1,Smax)
    scores = jnp.where(ok[None, None, None, None], scores, NEG_INF)
    out = _mix(scores, cv, cfg) @ p["wo"]
    return out, ck, cv
