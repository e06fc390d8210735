"""Plain reference of Mellum2-12B-A2.5B's decoder, held share of experts.

Written from the published config.json's equations, not from the program:
pre-RMSNorm (gain stored as 1 + g, eps from the configuration); GQA
attention (32 query heads over 4 key/value heads, head_dim 128), causal,
with a window of `sliding_window` keys on the "sliding_attention" layers
and none on the "full_attention" ones; RoPE on the two halves of each
head, the default on sliding layers and YaRN (arXiv:2309.00071, the
`rope_parameters.full_attention` numbers, cos and sin scaled by the
attention factor) on full ones; a sparse MLP on every layer -- softmax
over all experts, the top k renormalised, and the SwiGLU experts this chip
holds (experts 0 .. num_experts_held - 1), each applied to every token
with its routing weight (zero where it was not chosen): what the absent
experts would add is left out, as in the program; the Switch load-balance
loss at 0.01 (assumed); an untied head; mean token cross-entropy; AdamW
with global-norm clipping (`dense_lm.adamw`).  Everything in float32 at
`highest` precision; attention in query blocks, so (B, H, S, S) never
exists.  `fp8=True` is the control: every matrix product rounded as fp8
training rounds it (`dense_lm._mm`).

The parameter tree uses the names and stacking the program takes: one
scan period of `pos0..pos{P-1}` (P = the layer pattern's period), each
stacked over the periods; expert weights (E_held, ...) under `ffn`.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

from dense_lm import (_mm, _normal, _rms, adamw, host_batch, leaf_norms,
                      seed_key)

__all__ = ["init_state", "host_batch", "loss_and_grads", "adamw",
           "leaf_norms", "follow", "train_flops"]

AUX_COEF = 0.01
Q_BLOCK = 256


# ------------------------------------------------------------------ sizes
def dims(conf: dict) -> dict:
    L = conf["num_hidden_layers"]
    kinds = conf["layer_types"][:L]
    P = kinds.index("full_attention") + 1
    assert L % P == 0 and kinds == kinds[:P] * (L // P), kinds
    rp = conf["rope_parameters"]
    return {"L": L, "P": P, "kinds": kinds, "D": conf["hidden_size"],
            "H": conf["num_attention_heads"],
            "KV": conf["num_key_value_heads"], "hd": conf["head_dim"],
            "F": conf["moe_intermediate_size"], "V": conf["vocab_size"],
            "E": conf["num_experts"], "k": conf["num_experts_per_tok"],
            "E_held": conf["num_experts_held"],
            "W": conf["sliding_window"], "eps": conf["rms_norm_eps"],
            "rope": {"sliding_attention": rp["sliding_attention"],
                     "full_attention": rp["full_attention"]}}


# ------------------------------------------------------------------- init
def init_params(conf: dict, key, dtype):
    d = dims(conf)
    D, H, KV, hd, F, V = d["D"], d["H"], d["KV"], d["hd"], d["F"], d["V"]
    E, Eh = d["E"], d["E_held"]
    k_embed, k_blocks, k_head, _ = jax.random.split(key, 4)

    def layer(k):
        ka = jax.random.split(k, 8)
        return {
            "ln1": jnp.zeros((D,), dtype),
            "mix": {"wq": _normal(ka[0], (D, H * hd), D ** -0.5, dtype),
                    "wk": _normal(ka[1], (D, KV * hd), D ** -0.5, dtype),
                    "wv": _normal(ka[2], (D, KV * hd), D ** -0.5, dtype),
                    "wo": _normal(ka[3], (H * hd, D), (H * hd) ** -0.5,
                                  dtype)},
            "ln2": jnp.zeros((D,), dtype),
            "ffn": {"router": _normal(ka[4], (D, E), D ** -0.5,
                                      jnp.float32),
                    "wi_gate": _normal(ka[5], (Eh, D, F), D ** -0.5, dtype),
                    "wi_up": _normal(ka[6], (Eh, D, F), D ** -0.5, dtype),
                    "wo": _normal(ka[7], (Eh, F, D), F ** -0.5, dtype)},
        }

    def period(k):
        ks = jax.random.split(k, d["P"])
        return {f"pos{i}": layer(ks[i]) for i in range(d["P"])}

    blocks = jax.vmap(period)(jax.random.split(k_blocks, d["L"] // d["P"]))
    return {"embed": _normal(k_embed, (V, D), 0.02, dtype),
            "blocks": blocks,
            "final_norm": jnp.zeros((D,), dtype),
            "lm_head": _normal(k_head, (D, V), D ** -0.5, dtype)}


def init_state(conf: dict, seed: int, dtype=None):
    """The whole train state from the seed, in one jitted call (as
    `dense_lm.init_state`)."""
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
def inv_freq(rope: dict, hd: int):
    """RoPE frequencies of one layer kind: theta^(-2i/hd), or YaRN's blend
    of them with their interpolation (divided by the factor) over a
    linear ramp between the dimensions that turn beta_fast and beta_slow
    times over the original length.  Returns (freqs, cos/sin scale)."""
    theta = rope["rope_theta"]
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2 / hd)
    if rope["rope_type"] == "default":
        return freqs, 1.0
    assert rope["rope_type"] == "yarn", rope
    orig = rope["original_max_position_embeddings"]

    def dim(turns):
        return hd * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))
    lo = max(math.floor(dim(rope["beta_fast"])), 0)
    hi = min(math.ceil(dim(rope["beta_slow"])), hd - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - lo)
                    / max(hi - lo, 1e-3), 0.0, 1.0)
    freqs = freqs * (1.0 - ramp) + freqs / rope["factor"] * ramp
    return freqs, rope["attention_factor"]


def _rope(x, rope):
    """x (B, S, H, hd): rotate the first half against the second."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs, scale = inv_freq(rope, hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    c = (jnp.cos(ang) * scale)[None, :, None]
    s = (jnp.sin(ang) * scale)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _attend(q, k, v, window, fp8):
    """Causal softmax attention, query blocks against every key: q (B, S,
    H, hd), k/v (B, S, H, hd); key j is seen by query i when j <= i and,
    with a window W, i - j < W."""
    B, S, H, hd = q.shape
    bq = min(Q_BLOCK, S)
    kpos = jnp.arange(S)

    @jax.checkpoint
    def block(args):
        qb, i0 = args
        s = _mm("bqhd,bkhd->bhqk", qb, k, fp8) * hd ** -0.5
        qpos = i0 + jnp.arange(bq)
        ok = kpos[None, :] <= qpos[:, None]
        if window is not None:
            ok = ok & (qpos[:, None] - kpos[None, :] < window)
        w = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
        return _mm("bhqk,bkhd->bqhd", w, v, fp8)

    qs = q.reshape(B, S // bq, bq, H, hd).swapaxes(0, 1)
    o = jax.lax.map(block, (qs, jnp.arange(S // bq) * bq))
    return o.swapaxes(0, 1).reshape(B, S, H * hd)


def _experts(d, fp8, h, p):
    """The held experts' part of the sparse MLP, and the layer's Switch
    load-balance loss (over every expert and token)."""
    B, S, D = h.shape
    E, k, Eh = d["E"], d["k"], d["E_held"]
    probs = jax.nn.softmax(_mm("bsd,de->bse", h, p["router"], fp8), -1)
    top, sel = jax.lax.top_k(probs, k)
    top = top / jnp.sum(top, -1, keepdims=True)
    chosen = jax.nn.one_hot(sel, E, dtype=jnp.float32)    # (B, S, k, E)
    gate = jnp.einsum("bsk,bske->bse", top, chosen)        # 0 if not chosen

    @jax.checkpoint
    def expert(y, we):
        g, wg, wu, wo = we
        a = _mm("bsd,df->bsf", h, wg, fp8)
        b = _mm("bsd,df->bsf", h, wu, fp8)
        return y + g[..., None] * _mm("bsf,fd->bsd", jax.nn.silu(a) * b, wo,
                                      fp8), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                        (jnp.moveaxis(gate[..., :Eh], -1, 0), p["wi_gate"],
                         p["wi_up"], p["wo"]))
    frac = jnp.mean(jnp.sum(chosen, 2).reshape(-1, E), 0)
    me = jnp.mean(probs.reshape(-1, E), 0)
    return y, E * jnp.sum(me * jax.lax.stop_gradient(frac)) / k


def _layer(d, kind, fp8, x, p):
    B, S, D = x.shape
    H, KV, hd = d["H"], d["KV"], d["hd"]
    rope = d["rope"][kind]
    h = _rms(x, p["ln1"], d["eps"])
    q = _rope(_mm("bsd,de->bse", h, p["mix"]["wq"], fp8)
              .reshape(B, S, H, hd), rope)
    k = _rope(_mm("bsd,de->bse", h, p["mix"]["wk"], fp8)
              .reshape(B, S, KV, hd), rope)
    v = _mm("bsd,de->bse", h, p["mix"]["wv"], fp8).reshape(B, S, KV, hd)
    k = jnp.repeat(k, H // KV, axis=2)          # query head h -> kv h // G
    v = jnp.repeat(v, H // KV, axis=2)
    window = d["W"] if kind == "sliding_attention" else None
    o = _attend(q, k, v, window, fp8)
    x = x + _mm("bse,ed->bsd", o, p["mix"]["wo"], fp8)
    y, aux = _experts(d, fp8, _rms(x, p["ln2"], d["eps"]), p["ffn"])
    return x + y, aux


def loss_fn(conf: dict, params, tokens, labels, fp8: bool = False):
    """Mean token cross-entropy of the batch plus 0.01 x the layers' summed
    load-balance losses, all in float32."""
    d = dims(conf)
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    x = p32["embed"][tokens]
    aux = 0.0
    for li, kind in enumerate(d["kinds"]):
        p = jax.tree.map(lambda a: a[li // d["P"]],
                         p32["blocks"][f"pos{li % d['P']}"])
        x, a = jax.checkpoint(functools.partial(_layer, d, kind, fp8))(x, p)
        aux = aux + a
    h = _rms(x, p32["final_norm"], d["eps"])
    logits = _mm("bsd,dv->bsv", h, p32["lm_head"], fp8)
    ll = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - ll) + AUX_COEF * aux


def loss_and_grads(conf: dict, params, tokens, labels, *, fp8: bool = False):
    """Loss and float32 gradients over the whole batch at once (the
    load-balance loss is over the batch's tokens, not a mean of rows')."""
    return jax.value_and_grad(
        lambda p: loss_fn(conf, p, tokens, labels, fp8))(params)


@functools.lru_cache(maxsize=4)
def _programs(conf_json: str, fp8: bool):
    """The jitted init, step and change readers of one configuration,
    traced once per process (readings follow many seeds)."""
    conf = json.loads(conf_json)
    opt = conf["optimizer"]
    init = jax.jit(lambda k: init_params(conf, k,
                                         jnp.dtype(conf["param_dtype"])))

    @jax.jit
    def one(params, mu, nu, t, tokens, labels):
        with jax.default_matmul_precision("highest"):
            loss, g = loss_and_grads(conf, params, tokens, labels, fp8=fp8)
            new, mu, nu, gc = adamw(opt, g, mu, nu, params, t)
        return new, mu, nu, loss, leaf_norms(gc)

    change = jax.jit(lambda a, b: leaf_norms(jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b)))
    return init, one, change


def follow(conf: dict, seed: int, batches, *, fp8: bool = False,
           steps: int = 3) -> dict:
    """Train the reference from the seed over `batches` and return what
    the comparison reads (as `dense_lm.follow`)."""
    init, one, change = _programs(json.dumps(conf, sort_keys=True), fp8)
    params = init(seed_key(seed))
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
    dp = [float(x) for x in change(params, p0)]
    return {"loss": losses, "grad_norms": g1, "change_norms": dp,
            "leaves": [jax.tree_util.keystr(k) for k, _ in
                       jax.tree_util.tree_flatten_with_path(p0)[0]]}


# ------------------------------------------------------------ operations
def train_flops(conf: dict, batch: int, seq: int) -> int:
    """Operations one training step needs (forward and backward, no
    recomputation): 6 per active weight per token -- attention's
    projections, the router, the held experts at the expected
    k x E_held / E of them per token, and the head -- and for attention
    the score and value products, 2 operations each per head dimension
    and query-key pair, times 3 for the backward pass; a windowed layer's
    query q sees min(q + 1, W) keys, a full layer's q + 1."""
    d = dims(conf)
    D, H, KV, hd, F = d["D"], d["H"], d["KV"], d["hd"], d["F"]
    per_layer = (D * H * hd + 2 * D * KV * hd + H * hd * D + D * d["E"]
                 + d["k"] * d["E_held"] / d["E"] * 3 * D * F)
    active = d["L"] * per_layer + D * d["V"]
    full = seq * (seq + 1) // 2
    W = min(d["W"], seq)
    windowed = W * (W + 1) // 2 + (seq - W) * W
    pairs = sum(windowed if kind == "sliding_attention" else full
                for kind in d["kinds"])
    return int(6 * active * batch * seq + 3 * 2 * 2 * batch * H * hd * pairs)

