"""Shared neural-net primitives (pure JAX, pytree params)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# Sentinel window width meaning "full attention" (fits int32, > any seq len).
FULL_WINDOW = 1 << 30


def dtype_of(cfg):
    return jnp.dtype(cfg.dtype)


def pdtype_of(cfg):
    return jnp.dtype(cfg.param_dtype)


def rms_norm(x, gain, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + gain.astype(jnp.float32))).astype(dt)


def init_rms(d, dtype):
    return jnp.zeros((d,), dtype)          # gain stored as (1 + g)


def dense_init(key, shape, dtype, scale=None):
    fan_in = shape[0] if len(shape) >= 2 else 1
    if scale is None:
        scale = fan_in ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------- RoPE
# YaRN's ramp ends: the paper's alpha and beta (rotations over the
# original length), which the configs that use YaRN all keep
YARN_BETA_FAST, YARN_BETA_SLOW = 32.0, 1.0

def rope_angles(positions, head_dim, theta, *, yarn_factor=0.0,
                original_max=0):
    """positions: (...,) int -> cos/sin of shape (..., head_dim//2).

    yarn_factor s > 0: YaRN (arXiv:2309.00071) -- each frequency blends its
    extrapolated value theta^(-2i/d) with the interpolated one (divided by
    s) over a linear ramp between the dimensions that turn YARN_BETA_FAST
    and YARN_BETA_SLOW times over `original_max` positions; cos and sin
    are scaled by the attention factor 0.1 ln s + 1."""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if not yarn_factor:
        ang = positions.astype(jnp.float32)[..., None] * freqs
        return jnp.cos(ang), jnp.sin(ang)

    def dim_of(turns):
        return (head_dim * math.log(original_max / (turns * 2 * math.pi))
                / (2 * math.log(theta)))
    lo = max(math.floor(dim_of(YARN_BETA_FAST)), 0)
    hi = min(math.ceil(dim_of(YARN_BETA_SLOW)), head_dim - 1)
    hi = hi if hi > lo else lo + 0.001
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - lo) / (hi - lo),
                    0.0, 1.0)
    freqs = freqs / yarn_factor * ramp + freqs * (1.0 - ramp)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    scale = 0.1 * math.log(yarn_factor) + 1.0
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def apply_rope(x, cos, sin):
    """x: (B, S, H, hd); cos/sin: (S, hd//2) or (B, S, hd//2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == 2:                       # (S, half) -> broadcast over B, H
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    else:                                   # (B, S, half)
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    xf = x.astype(jnp.float32)
    x1f, x2f = xf[..., :half], xf[..., half:]
    out = jnp.concatenate([x1f * cos - x2f * sin, x2f * cos + x1f * sin], -1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------- MLP
def init_mlp(key, cfg):
    D, F = cfg.d_model, cfg.d_ff
    pd = pdtype_of(cfg)
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wi_gate": dense_init(k1, (D, F), pd),
        "wi_up": dense_init(k2, (D, F), pd),
        "wo": dense_init(k3, (F, D), pd),
    }


def mlp(p, x):
    h = jax.nn.silu(x @ p["wi_gate"]) * (x @ p["wi_up"])
    return h @ p["wo"]


def cross_entropy(logits, labels, mask=None):
    """Mean CE in fp32. logits (..., V), labels (...) int32."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - ll
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def chunked_cross_entropy(h, w_out, labels, chunk, mask=None, unroll=False):
    """CE over sequence chunks without materializing (B, S, V).

    h: (B, S, D) final hidden states; w_out: (D, V); labels: (B, S).
    """
    B, S, D = h.shape
    n = max(1, S // chunk)
    while S % n:
        n -= 1
    hc = h.reshape(B, n, S // n, D).swapaxes(0, 1)          # (n, B, c, D)
    lc = labels.reshape(B, n, S // n).swapaxes(0, 1)
    mc = (mask.reshape(B, n, S // n).swapaxes(0, 1).astype(jnp.float32)
          if mask is not None else jnp.ones_like(lc, jnp.float32))

    def body(carry, xs):
        hh, ll, mm = xs
        logits = (hh @ w_out).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, ll[..., None], axis=-1)[..., 0]
        nll = (logz - tgt) * mm
        tot, cnt = carry
        return (tot + jnp.sum(nll), cnt + jnp.sum(mm)), None

    carry = (jnp.zeros(()), jnp.zeros(()))
    if unroll:                                   # dry-run FLOP accounting
        for i in range(n):
            carry, _ = body(carry, (hc[i], lc[i], mc[i]))
        tot, cnt = carry
    else:
        (tot, cnt), _ = jax.lax.scan(body, carry, (hc, lc, mc))
    return tot / jnp.maximum(cnt, 1.0)
