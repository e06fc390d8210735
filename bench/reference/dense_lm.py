"""Plain reference of the dense decoder the OPT configurations run.

Written from the block's equations, not from the program: pre-RMSNorm
(gain stored as 1 + g, eps from the configuration), RoPE on the two halves
of each head, causal softmax attention with grouped key/value heads, a
gated SiLU MLP, an untied head, mean token cross-entropy, and AdamW with
global-norm clipping.  Matrix products run in float32 at `highest`
precision.  `fp8=True` is the control: every matrix product computed as
fp8 training computes it, both operands rounded to float8_e4m3fn under a
per-tensor scale forward, the incoming gradient to float8_e5m2 backward.

The benchmark also makes the weights here, from the seed, in one jitted
call: the state the program trains is built by `init_state`, and the
reference re-makes the same weights itself after the window.

The parameter tree uses the names and stacking the trained program takes
(`blocks.pos0` holds every layer, stacked on a leading axis).
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
F8_GRAD = jnp.float8_e5m2
F8_GRAD_MAX = 57344.0


# ------------------------------------------------------------------ sizes
def dims(conf: dict) -> dict:
    D = conf["hidden_size"]
    H = conf["num_attention_heads"]
    return {"L": conf["num_hidden_layers"], "D": D, "H": H,
            "KV": conf.get("num_key_value_heads", H),
            "hd": conf.get("head_dim", D // H), "F": conf["ffn_dim"],
            "V": conf["vocab_size"], "theta": conf["rope_theta"],
            "eps": conf["rms_norm_eps"]}


# ------------------------------------------------------------------- init
def seed_key(seed: int):
    """The run's root key: the low 32 bits as JAX takes them, the rest
    folded in, so seeds past 2**32 stay distinct."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return key if seed >> 32 == 0 else jax.random.fold_in(key, seed >> 32)


def _normal(key, shape, scale, dt):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dt)


def init_params(conf: dict, key, dtype):
    d = dims(conf)
    D, H, KV, hd, F, V = d["D"], d["H"], d["KV"], d["hd"], d["F"], d["V"]
    k_embed, k_blocks, k_head, _ = jax.random.split(key, 4)

    def layer(k):
        (k,) = jax.random.split(k, 1)
        k_mix, k_ffn = jax.random.split(k, 2)
        ka = jax.random.split(k_mix, 4)
        km = jax.random.split(k_ffn, 3)
        return {
            "ln1": jnp.zeros((D,), dtype),
            "mix": {"wq": _normal(ka[0], (D, H * hd), D ** -0.5, dtype),
                    "wk": _normal(ka[1], (D, KV * hd), D ** -0.5, dtype),
                    "wv": _normal(ka[2], (D, KV * hd), D ** -0.5, dtype),
                    "wo": _normal(ka[3], (H * hd, D), (H * hd) ** -0.5,
                                  dtype)},
            "ln2": jnp.zeros((D,), dtype),
            "ffn": {"wi_gate": _normal(km[0], (D, F), D ** -0.5, dtype),
                    "wi_up": _normal(km[1], (D, F), D ** -0.5, dtype),
                    "wo": _normal(km[2], (F, D), F ** -0.5, dtype)},
        }

    blocks = jax.vmap(layer)(jax.random.split(k_blocks, d["L"]))
    return {"embed": _normal(k_embed, (V, D), 0.02, dtype),
            "blocks": {"pos0": blocks},
            "final_norm": jnp.zeros((D,), dtype),
            "lm_head": _normal(k_head, (D, V), D ** -0.5, dtype)}


def init_state(conf: dict, seed: int, dtype=None):
    """The whole train state from the seed, in one jitted call: weights in
    the configuration's parameter type, zero Adam moments in its moment
    type, step 0 and the data-order key."""
    dtype = jnp.dtype(dtype or conf["param_dtype"])
    mdt = jnp.dtype(conf["moment_dtype"])

    @jax.jit
    def make(key, rng):
        params = init_params(conf, key, dtype)
        zeros = lambda p: jnp.zeros(p.shape, mdt)        # noqa: E731
        return {"params": params,
                "opt_state": {"mu": jax.tree.map(zeros, params),
                              "nu": jax.tree.map(zeros, params),
                              "step": jnp.zeros((), jnp.int32)},
                "step": jnp.zeros((), jnp.int32),
                "rng": rng}

    return make(seed_key(seed), jax.random.PRNGKey((seed + 1) & 0xFFFFFFFF))


# ---------------------------------------------------------------- forward
def _round(x, dtype, top):
    """x rounded to an fp8 type under a per-tensor scale (amax -> top)."""
    s = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * s).astype(dtype).astype(jnp.float32) / s


def _dot(spec, a, b):
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32,
                      precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dot_fp8(spec, a, b):
    """A matrix product computed in fp8 as fp8 training computes it: both
    operands in e4m3 forward, the incoming gradient in e5m2 backward."""
    return _dot(spec, _round(a, F8, F8_MAX), _round(b, F8, F8_MAX))


def _dot_fp8_fwd(spec, a, b):
    qa, qb = _round(a, F8, F8_MAX), _round(b, F8, F8_MAX)
    return _dot(spec, qa, qb), (qa, qb)


def _dot_fp8_bwd(spec, res, g):
    qa, qb = res
    _, vjp = jax.vjp(lambda x, y: _dot(spec, x, y), qa, qb)
    return vjp(_round(g, F8_GRAD, F8_GRAD_MAX))


_dot_fp8.defvjp(_dot_fp8_fwd, _dot_fp8_bwd)


def _mm(spec, a, b, fp8):
    return _dot_fp8(spec, a, b) if fp8 else _dot(spec, a, b)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + g)


def _rope(x, theta):
    """x (B, S, H, hd): rotate the first half against the second."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    c, s = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _layer(d, fp8, x, p):
    B, S, D = x.shape
    H, KV, hd = d["H"], d["KV"], d["hd"]
    h = _rms(x, p["ln1"], d["eps"])
    q = _rope(_mm("bsd,de->bse", h, p["mix"]["wq"], fp8)
              .reshape(B, S, H, hd), d["theta"])
    k = _rope(_mm("bsd,de->bse", h, p["mix"]["wk"], fp8)
              .reshape(B, S, KV, hd), d["theta"])
    v = _mm("bsd,de->bse", h, p["mix"]["wv"], fp8).reshape(B, S, KV, hd)
    k = jnp.repeat(k, H // KV, axis=2)          # query head h -> kv h // G
    v = jnp.repeat(v, H // KV, axis=2)
    s = _mm("bqhd,bkhd->bhqk", q, k, fp8) * hd ** -0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = _mm("bhqk,bkhd->bqhd", w, v, fp8).reshape(B, S, H * hd)
    x = x + _mm("bse,ed->bsd", o, p["mix"]["wo"], fp8)
    h = _rms(x, p["ln2"], d["eps"])
    f = jax.nn.silu(_mm("bsd,df->bsf", h, p["ffn"]["wi_gate"], fp8)) \
        * _mm("bsd,df->bsf", h, p["ffn"]["wi_up"], fp8)
    return x + _mm("bsf,fd->bsd", f, p["ffn"]["wo"], fp8)


def loss_fn(conf: dict, params, tokens, labels, fp8: bool = False):
    """Mean token cross-entropy of one block of rows, all in float32."""
    d = dims(conf)
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    x = p32["embed"][tokens]
    body = jax.checkpoint(functools.partial(_layer, d, fp8))
    x, _ = jax.lax.scan(lambda c, p: (body(c, p), None), x,
                        p32["blocks"]["pos0"])
    h = _rms(x, p32["final_norm"], d["eps"])
    logits = _mm("bsd,dv->bsv", h, p32["lm_head"], fp8)
    ll = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - ll)


def loss_and_grads(conf: dict, params, tokens, labels, *, fp8: bool = False):
    """Loss and float32 gradients over the whole batch, one sequence at a
    time (the mean over sequences of equal length is the mean of theirs)."""
    B = tokens.shape[0]
    vg = jax.value_and_grad(
        lambda p, t, lab: loss_fn(conf, p, t[None], lab[None], fp8))
    zeros = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params)

    def body(carry, xs):
        tot, acc = carry
        lv, g = vg(params, *xs)
        return (tot + lv, jax.tree.map(jnp.add, acc, g)), None

    (tot, g), _ = jax.lax.scan(body, (jnp.zeros(()), zeros), (tokens, labels))
    return tot / B, jax.tree.map(lambda a: a / B, g)


# -------------------------------------------------------------- optimizer
def adamw(opt: dict, grads, mu, nu, params, t):
    """One AdamW update with global-norm clipping; returns (params in
    their stored type, mu, nu, clipped grads)."""
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    clip = jnp.minimum(1.0, opt["grad_clip"] / (gnorm + 1e-9))
    g = jax.tree.map(lambda a: a * clip, grads)
    b1, b2 = opt["b1"], opt["b2"]
    mu = jax.tree.map(lambda m, a: b1 * m + (1 - b1) * a, mu, g)
    nu = jax.tree.map(lambda v, a: b2 * v + (1 - b2) * a * a, nu, g)
    tf = jnp.float32(t)

    def upd(p, m, v):
        delta = (m / (1 - b1 ** tf)) / (jnp.sqrt(v / (1 - b2 ** tf))
                                        + opt["eps"])
        delta = delta + opt["weight_decay"] * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - opt["lr"] * delta).astype(p.dtype)

    return jax.tree.map(upd, params, mu, nu), mu, nu, g


def leaf_norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
            for a in jax.tree.leaves(tree)]


def follow(conf: dict, seed: int, batches, *, fp8: bool = False,
           steps: int = 3) -> dict:
    """Train the reference from the seed over `batches` (host (tokens,
    labels) pairs) and return what the comparison reads: each step's
    loss, the leaf norms of the first clipped gradient, and the leaf norms
    of the weights' change after `steps` steps."""
    opt = conf["optimizer"]
    params = jax.jit(lambda k: init_params(
        conf, k, jnp.dtype(conf["param_dtype"])))(seed_key(seed))

    @jax.jit
    def one(params, mu, nu, t, tokens, labels):
        with jax.default_matmul_precision("highest"):
            loss, g = loss_and_grads(conf, params, tokens, labels, fp8=fp8)
            new, mu, nu, gc = adamw(opt, g, mu, nu, params, t)
        return new, mu, nu, loss, leaf_norms(gc)

    p0 = params
    zeros = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params)
    mu, nu = zeros, zeros
    losses, g1 = [], None
    for t, (tok, lab) in enumerate(batches[:steps], start=1):
        params, mu, nu, loss, gn = one(params, mu, nu, t, jnp.asarray(tok),
                                       jnp.asarray(lab))
        losses.append(float(loss))
        if g1 is None:
            g1 = [float(x) for x in gn]
    change = jax.jit(lambda a, b: leaf_norms(jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b)))
    dp = [float(x) for x in change(params, p0)]
    return {"loss": losses, "grad_norms": g1, "change_norms": dp,
            "leaves": [jax.tree_util.keystr(k) for k, _ in
                       jax.tree_util.tree_flatten_with_path(p0)[0]]}


def host_batch(conf: dict, seed: int, step: int, batch: int, seq: int):
    """Step `step`'s (tokens, labels) of the seed's stream."""
    rng = np.random.default_rng(hash((seed, step)) % (2 ** 31))
    V = conf["vocab_size"]
    tok = rng.integers(0, V, size=(batch, seq), dtype=np.int64)
    lab = rng.integers(0, V, size=(batch, seq), dtype=np.int64)
    return tok.astype(np.int32), lab.astype(np.int32)
