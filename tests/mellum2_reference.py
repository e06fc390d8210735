"""Plain float32 reference of Mellum2's decoder for the CPU tests.

Written from the published equations (hf:JetBrains/Mellum2-12B-A2.5B
config.json), not from the program; it reads only numbers from a
ModelConfig and the program's parameter tree (`blocks.pos{i}` stacked over
periods).  Every `global_every`-th layer is full attention with YaRN RoPE,
the others see `sliding_window` keys with the default RoPE; every MLP is
sparse: softmax over all experts, the top k renormalised, and the experts
held here (ids 0 .. E_held - 1) applied to every token with their routing
weight.  The Switch load-balance loss (0.01, the program's) is over the
batch's tokens.  The whole (S, S) score matrix is formed: small sizes only.
"""
from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def yarn_freqs(hd, theta, factor, orig, beta_fast, beta_slow):
    """YaRN's inverse frequencies (arXiv:2309.00071, eq. 22-23), numpy."""
    pos = theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    extra, inter = 1.0 / pos, 1.0 / (factor * pos)

    def dim(turns):
        return hd * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))
    lo = max(math.floor(dim(beta_fast)), 0)
    hi = min(math.ceil(dim(beta_slow)), hd - 1)
    ramp = np.clip((np.arange(hd // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    keep = 1.0 - ramp                      # the extrapolated share
    return inter * (1 - keep) + extra * keep


def _rms(x, g):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) \
        * (1.0 + g)


def _rope(x, cfg, full):
    S, hd = x.shape[1], x.shape[-1]
    if full and cfg.yarn_factor:
        # the published beta_fast 32, beta_slow 1, attention_factor
        freqs = jnp.asarray(yarn_freqs(
            hd, cfg.rope_theta, cfg.yarn_factor, cfg.yarn_original_max,
            32.0, 1.0), jnp.float32)
        scale = 1.2772588722239782
    else:
        freqs = cfg.rope_theta ** (-jnp.arange(hd // 2) * 2.0 / hd)
        scale = 1.0
    ang = jnp.arange(S)[:, None] * freqs
    c = (jnp.cos(ang) * scale)[None, :, None]
    s = (jnp.sin(ang) * scale)[None, :, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def attention(cfg, p, h, full):
    B, S, _ = h.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = _rope(jnp.dot(h, p["wq"], precision=HI).reshape(B, S, H, hd),
              cfg, full)
    k = _rope(jnp.dot(h, p["wk"], precision=HI).reshape(B, S, KV, hd),
              cfg, full)
    v = jnp.dot(h, p["wv"], precision=HI).reshape(B, S, KV, hd)
    k, v = (jnp.repeat(a, H // KV, axis=2) for a in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) * hd ** -0.5
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    ok = (j <= i) if full else (j <= i) & (i - j < cfg.sliding_window)
    w = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), -1)
    o = jnp.einsum("bhqk,bkhd->bqhd", w, v, precision=HI)
    return jnp.dot(o.reshape(B, S, H * hd), p["wo"], precision=HI)


def experts(cfg, p, h, held=None):
    """(the held experts' part of the sparse MLP, its load-balance loss).
    held: the global ids of the experts in p's expert axis."""
    E, k = cfg.num_experts, cfg.experts_per_token
    held = range(p["wi_gate"].shape[0]) if held is None else held
    probs = jax.nn.softmax(jnp.dot(h, p["router"], precision=HI), -1)
    top, sel = jax.lax.top_k(probs, k)
    top = top / jnp.sum(top, -1, keepdims=True)
    chosen = jax.nn.one_hot(sel, E)
    gate = jnp.einsum("...k,...ke->...e", top, chosen)
    y = jnp.zeros_like(h)
    for i, e in enumerate(held):
        a = jnp.dot(h, p["wi_gate"][i], precision=HI)
        b = jnp.dot(h, p["wi_up"][i], precision=HI)
        y = y + gate[..., e:e + 1] * jnp.dot(jax.nn.silu(a) * b, p["wo"][i],
                                             precision=HI)
    frac = jnp.mean(jnp.sum(chosen, -2).reshape(-1, E), 0)
    me = jnp.mean(probs.reshape(-1, E), 0)
    return y, E * jnp.sum(me * frac) / k


def loss(cfg, params, tokens, labels):
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    x = p32["embed"][tokens]
    P = len(p32["blocks"])
    aux = 0.0
    for li in range(cfg.num_layers):
        p = jax.tree.map(lambda a: a[li // P], p32["blocks"][f"pos{li % P}"])
        full = (li + 1) % cfg.global_every == 0
        x = x + attention(cfg, p["mix"], _rms(x, p["ln1"]), full)
        y, a = experts(cfg, p["ffn"], _rms(x, p["ln2"]))
        x, aux = x + y, aux + a
    logits = jnp.dot(_rms(x, p32["final_norm"]), p32["lm_head"],
                     precision=HI)
    ll = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - ll) + 0.01 * aux
