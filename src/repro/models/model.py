"""Model assembly: init / forward / prefill / decode for every family.

Layer stacks are *scanned* (stacked params, `lax.scan`) so the HLO stays
compact for 95-layer / trillion-parameter configs.  Heterogeneous hybrids
(Jamba) and local:global attention patterns (Mellum2, Gemma3) scan over
*periods* whose body unrolls the static per-position layer kinds, so each
position's window and RoPE kind are Python values.  Only a pattern that
does not tile the depth scans one layer at a time with the window as a
scanned int32 (FULL_WINDOW sentinel for global layers).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ATTN, ModelConfig
from repro.dist.api import shard
from repro.models.attention import attention, attention_decode, init_attn
from repro.models.layers import (
    FULL_WINDOW, chunked_cross_entropy, cross_entropy, dense_init, dtype_of,
    init_mlp, init_rms, mlp, pdtype_of, rms_norm,
)
from repro.models.moe import init_moe, moe_ffn
from repro.models.ssm import init_ssm, ssm_block, ssm_decode


# ===================================================================== init
def window_array(cfg: ModelConfig, count=None, offset=0):
    vals = [cfg.layer_window(offset + i) or FULL_WINDOW
            for i in range(count or cfg.num_layers)]
    return jnp.asarray(vals, jnp.int32)


def _init_layer(cfg: ModelConfig, key, idx: int):
    """One layer's params; `idx` decides kind/moe via the static pattern."""
    pd = pdtype_of(cfg)
    D = cfg.d_model
    ks = jax.random.split(key, 2)
    p = {"ln1": init_rms(D, pd)}
    if cfg.layer_kind(idx) == ATTN:
        p["mix"] = init_attn(ks[0], cfg)
    else:
        p["mix"] = init_ssm(ks[0], cfg)
    if cfg.d_ff:
        p["ln2"] = init_rms(D, pd)
        p["ffn"] = (init_moe(ks[1], cfg) if cfg.layer_is_moe(idx)
                    else init_mlp(ks[1], cfg))
    return p


def _stack_period(cfg: ModelConfig):
    """(period, n_periods) for the scan structure."""
    if cfg.family == "hybrid" and cfg.attn_period:
        period = cfg.attn_period
        if cfg.num_experts:
            # the scan body must see a pattern that repeats exactly
            period = math.lcm(period, cfg.moe_every)
        assert cfg.num_layers % period == 0, (cfg.name, period)
        return period, cfg.num_layers // period
    if cfg.sliding_window is not None and cfg.global_every:
        period = cfg.global_every
        if cfg.num_experts:
            period = math.lcm(period, cfg.moe_every)
        if cfg.num_layers % period == 0:
            return period, cfg.num_layers // period
    return 1, cfg.num_layers


def static_windows(cfg: ModelConfig):
    """Each period position's window as a Python value (None: full), or
    None where the period does not fix it (the scanned int32 then)."""
    period, _ = _stack_period(cfg)
    if (cfg.sliding_window is None or not cfg.global_every
            or period % cfg.global_every == 0):
        return [cfg.layer_window(i) for i in range(period)]
    return None


def init_params(cfg: ModelConfig, key):
    pd = pdtype_of(cfg)
    D, V = cfg.d_model, cfg.vocab_size
    k_embed, k_blocks, k_head, k_proj = jax.random.split(key, 4)
    params = {}
    if cfg.embed_inputs:
        params["embed"] = dense_init(k_embed, (V, D), pd, scale=0.02)
    if not cfg.embed_inputs or cfg.num_patches:
        params["proj_in"] = dense_init(k_proj, (D, D), pd)
    period, n_periods = _stack_period(cfg)
    keys = jax.random.split(k_blocks, n_periods)

    def init_period(k):
        pks = jax.random.split(k, period)
        return {f"pos{i}": _init_layer(cfg, pks[i], i) for i in range(period)}

    params["blocks"] = jax.vmap(init_period)(keys)
    params["final_norm"] = init_rms(D, pd)
    if cfg.is_encoder or not cfg.tie_embeddings:
        params["lm_head"] = dense_init(k_head, (D, V), pd)
    return params


# ===================================================================== fwd
def _acc0(cfg):
    """What the layer stack sums (and maxes) alongside h."""
    acc = {"aux": jnp.zeros((), jnp.float32)}
    if cfg.num_experts:
        acc.update(moe_rows_here=jnp.zeros((), jnp.int32),
                   moe_max_load=jnp.zeros((), jnp.float32))
    return acc


def merge_stats(acc, new):
    """Fold one layer's (or microbatch's) counters into `acc`: sums, but
    the max of `moe_max_load`."""
    return {k: (jnp.maximum(v, new[k]) if k == "moe_max_load" else
                v + new[k]) if k in new else v
            for k, v in acc.items()}


@jax.named_scope("mlp")
def _ffn_apply(cfg, p, idx, h):
    """Returns (out, counters): `aux`, and the MoE load on MoE layers."""
    if not cfg.d_ff:
        return jnp.zeros_like(h), {"aux": jnp.zeros((), jnp.float32)}
    h_in = rms_norm(h, p["ln2"])
    if cfg.layer_is_moe(idx):
        out, aux, stats = moe_ffn(p["ffn"], cfg, h_in)
        return out, {"aux": aux, **stats}
    return mlp(p["ffn"], h_in), {"aux": jnp.zeros((), jnp.float32)}


def _layer_full(cfg, p, idx, h, w, positions, collect_cache,
                static_idx=None, unroll=False):
    """One layer on the full sequence. Returns (h, counters, cache_entry).

    w: the layer's window (None, an int, or traced; `attention`).
    static_idx: the *global* layer index when it is statically known
    (unrolled dry-run) — enables exact banded attention per layer.
    """
    h = shard(h, P(("pod", "data"), None, None))
    if cfg.layer_kind(idx) == ATTN:
        band = None
        if cfg.banded_attention:
            if static_idx is not None:
                band = cfg.layer_window(static_idx)   # None on global layers
            elif isinstance(w, int):
                band = w
        a, (k, v) = attention(p["mix"], cfg, rms_norm(h, p["ln1"]),
                              window=w, positions=positions, band=band,
                              unroll=unroll)
        entry = ({"k": k, "v": v} if collect_cache else
                 {})
    else:
        a, (conv_state, h_final) = ssm_block(p["mix"], cfg,
                                             rms_norm(h, p["ln1"]),
                                             chunk=cfg.ssd_chunk)
        entry = ({"conv": conv_state, "h": h_final} if collect_cache else {})
    h = h + a
    f, stats = _ffn_apply(cfg, p, idx, h)
    h = h + f
    return h, stats, entry


def _layer_decode(cfg, p, idx, h, w, index, entry):
    """One-token step against this layer's cache slice."""
    if cfg.layer_kind(idx) == ATTN:
        a, ck, cv = attention_decode(p["mix"], cfg, rms_norm(h, p["ln1"]),
                                     entry["k"], entry["v"],
                                     window=w, index=index)
        new_entry = {"k": ck, "v": cv}
    else:
        a, conv_state, hs = ssm_decode(p["mix"], cfg, rms_norm(h, p["ln1"]),
                                       entry["conv"], entry["h"])
        new_entry = {"conv": conv_state, "h": hs}
    h = h + a
    f, _ = _ffn_apply(cfg, p, idx, h)
    return h + f, new_entry


def _windows(cfg):
    """(scanned windows (n_periods, period) or None, static per-position
    windows or None): exactly one of the two is given."""
    period, n_periods = _stack_period(cfg)
    static = static_windows(cfg)
    if static is not None:
        return None, static
    return window_array(cfg).reshape(n_periods, period), None


def _scan_blocks(cfg, params, h, positions, *, collect_cache=False,
                 remat=False, unroll=False):
    period, n_periods = _stack_period(cfg)
    win, static = _windows(cfg)
    policy = (jax.checkpoint_policies.dots_saveable
              if cfg.remat_policy == "dots" else None)

    def position(i, sidx):
        def layer(p, h, w_scanned):
            w = static[i] if static is not None else w_scanned
            return _layer_full(cfg, p, i, h, w, positions, collect_cache,
                               static_idx=sidx, unroll=unroll)
        # remat per layer: one layer's activations live at a time,
        # whatever the period
        return jax.checkpoint(layer, policy=policy) if remat else layer

    def make_body(period_idx=None):
        def body(carry, xs):
            h, acc = carry
            p_period, w_period = xs
            entries = {}
            for i in range(period):
                sidx = (None if period_idx is None
                        else period_idx * period + i)
                h, st, e = position(i, sidx)(
                    p_period[f"pos{i}"], h,
                    None if w_period is None else w_period[i])
                acc = merge_stats(acc, st)
                if collect_cache:
                    entries[f"pos{i}"] = e
            return (h, acc), entries
        return body

    carry0 = (h, _acc0(cfg))
    if unroll:
        # Dry-run mode: XLA's cost analysis counts a while-loop body once,
        # so roofline FLOPs are extracted from the unrolled program.  The
        # static layer index also enables exact per-layer banded attention.
        carry = carry0
        entries_list = []
        for i in range(n_periods):
            xs_i = (jax.tree.map(lambda a: a[i], params["blocks"]),
                    None if win is None else win[i])
            carry, entries = make_body(i)(carry, xs_i)
            entries_list.append(entries)
        h, acc = carry
        caches = (jax.tree.map(lambda *xs: jnp.stack(xs), *entries_list)
                  if collect_cache else {})
        return h, acc, caches
    (h, acc), caches = jax.lax.scan(make_body(), carry0,
                                    (params["blocks"], win))
    return h, acc, caches


def embed_batch(cfg: ModelConfig, params, batch):
    """-> (x (B,S,D), labels, loss_mask)."""
    dt = dtype_of(cfg)
    if cfg.family == "vlm":
        patches = batch["patches"].astype(dt) @ params["proj_in"]
        tok = params["embed"][batch["tokens"]].astype(dt)
        x = jnp.concatenate([patches, tok], axis=1)
        labels = batch["labels"]
        Bp = patches.shape[1]
        mask = jnp.concatenate(
            [jnp.zeros((x.shape[0], Bp), bool),
             jnp.ones((x.shape[0], x.shape[1] - Bp), bool)], axis=1)
        return x, labels, mask
    if not cfg.embed_inputs:                    # audio frames
        x = batch["frames"].astype(dt) @ params["proj_in"]
        return x, batch["labels"], batch.get("mask")
    x = params["embed"][batch["tokens"]].astype(dt)
    return x, batch["labels"], None


def _lm_head_w(cfg, params):
    if "lm_head" in params:
        return params["lm_head"]
    return params["embed"].T


def forward(cfg: ModelConfig, params, batch, *, collect_cache=False,
            remat=None, unroll=False):
    """Full-sequence forward. Returns (loss, aux_dict)."""
    with jax.named_scope("embed"):
        x, labels, mask = embed_batch(cfg, params, batch)
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)
    remat = cfg.remat if remat is None else remat
    h, acc, caches = _scan_blocks(cfg, params, x, positions,
                                  collect_cache=collect_cache, remat=remat,
                                  unroll=unroll)
    with jax.named_scope("head_loss"):
        h = rms_norm(h, params["final_norm"])
        w_out = _lm_head_w(cfg, params)
        if cfg.chunked_ce:
            loss = chunked_cross_entropy(h, w_out, labels, cfg.chunked_ce,
                                         mask, unroll=unroll)
        else:
            logits = h @ w_out
            logits = shard(logits, P(("pod", "data"), None, "model"))
            loss = cross_entropy(logits, labels, mask)
    loss = loss + 0.01 * acc["aux"]
    out = {"loss": loss, **acc}
    if collect_cache:
        out["cache"] = caches
    return loss, out


def logits_fn(cfg: ModelConfig, params, batch, *, unroll=False):
    """Last-position logits (used by prefill and tests)."""
    x, _, _ = embed_batch(cfg, params, batch)
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)
    h, _, caches = _scan_blocks(cfg, params, x, positions, collect_cache=True,
                                remat=False, unroll=unroll)
    h = rms_norm(h, params["final_norm"])
    logits = h[:, -1:, :] @ _lm_head_w(cfg, params)
    return logits, caches


# ===================================================================== cache
def init_cache(cfg: ModelConfig, batch_size: int, max_seq: int):
    """Zeroed decode cache pytree, stacked over scan periods."""
    period, n_periods = _stack_period(cfg)
    dt = dtype_of(cfg)
    entries = {}
    for i in range(period):
        if cfg.layer_kind(i) == ATTN:
            S = max_seq
            if cfg.window_kv_cache and cfg.layer_window(i) is not None:
                S = min(max_seq, cfg.layer_window(i))
            shape = (n_periods, batch_size, S, cfg.num_kv_heads, cfg.head_dim)
            entries[f"pos{i}"] = {"k": jnp.zeros(shape, dt),
                                  "v": jnp.zeros(shape, dt)}
        else:
            ch = cfg.d_inner + 2 * cfg.ssm_state
            entries[f"pos{i}"] = {
                "conv": jnp.zeros((n_periods, batch_size,
                                   cfg.ssm_conv_width - 1, ch), dt),
                "h": jnp.zeros((n_periods, batch_size, cfg.ssm_heads,
                                cfg.ssm_head_dim, cfg.ssm_state),
                               jnp.float32),
            }
    return {"entries": entries, "index": jnp.zeros((), jnp.int32)}


def decode_step(cfg: ModelConfig, params, cache, tokens, *, unroll=False):
    """One decode step. tokens: (B, 1) int32 -> (logits (B,1,V), new cache)."""
    period, n_periods = _stack_period(cfg)
    index = cache["index"]
    x = params["embed"][tokens].astype(dtype_of(cfg))
    win, static = _windows(cfg)

    def body(h, xs):
        p_period, w_period, entries = xs
        new_entries = {}
        for i in range(period):
            w = static[i] if static is not None else w_period[i]
            h, ne = _layer_decode(cfg, p_period[f"pos{i}"], i, h, w, index,
                                  entries[f"pos{i}"])
            new_entries[f"pos{i}"] = ne
        return h, new_entries

    if unroll:
        h = x
        ne_list = []
        for i in range(n_periods):
            xs_i = (jax.tree.map(lambda a: a[i], params["blocks"]),
                    None if win is None else win[i],
                    jax.tree.map(lambda a: a[i], cache["entries"]))
            h, ne = body(h, xs_i)
            ne_list.append(ne)
        new_entries = jax.tree.map(lambda *xs: jnp.stack(xs), *ne_list)
    else:
        h, new_entries = jax.lax.scan(
            body, x, (params["blocks"], win, cache["entries"]))
    h = rms_norm(h, params["final_norm"])
    logits = h @ _lm_head_w(cfg, params)
    return logits, {"entries": new_entries, "index": index + 1}
