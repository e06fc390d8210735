"""Top-k mixture-of-experts with capacity-based gather dispatch.

Tokens are sorted by routed expert (stable), ranked within each expert group,
and gathered into an (E, C+1, D) buffer (slot C absorbs capacity overflow;
dropped tokens contribute zero via a masked combine weight).  The expert
einsums carry sharding constraints so the E axis maps onto the "model"
(expert-parallel) mesh axis and the capacity axis onto "data" — GSPMD then
materializes the dispatch as all-to-all-style collectives rather than a full
replication.  Correctness is checked against a per-expert python-loop oracle
in tests (including the drop rule).
"""
from __future__ import annotations

import math
import threading

import numpy as np

import jax
import jax.numpy as jnp

from repro.analyze.lockgraph import named_lock
from repro.dist.api import shard
from jax.sharding import PartitionSpec as P

from repro.models.layers import dense_init, pdtype_of


def init_moe(key, cfg):
    D, F, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    pd = pdtype_of(cfg)
    ks = jax.random.split(key, 4)
    return {
        "router": dense_init(ks[0], (D, E), jnp.float32),
        "wi_gate": dense_init(ks[1], (E, D, F), pd),
        "wi_up": dense_init(ks[2], (E, D, F), pd),
        "wo": dense_init(ks[3], (E, F, D), pd),
    }


def _capacity(T, k, E, factor):
    return max(1, int(math.ceil(T * k / E * factor)))


def _dispatch_compute(xf, probs, w, sel, wi_gate, wi_up, wo, C):
    """Capacity-gather dispatch + expert einsums + weighted combine.

    xf: (T, D); w/sel: (T, k) routing weights / expert ids (ids may exceed
    the local expert count E_loc = wi_gate.shape[0] — those pairs are
    masked out, which is how the expert-parallel path drops non-local
    pairs).  Returns (T, D).
    """
    T, D = xf.shape
    E_loc = wi_gate.shape[0]
    k = sel.shape[1]
    Tk = T * k

    eids = sel.reshape(Tk)
    local = eids < E_loc
    eids = jnp.where(local, eids, E_loc)                 # trash expert
    order = jnp.argsort(eids, stable=True)
    sorted_eids = eids[order]
    group_start = jnp.searchsorted(sorted_eids,
                                   jnp.arange(E_loc, dtype=eids.dtype))
    rank = jnp.arange(Tk, dtype=jnp.int32) - group_start[
        jnp.minimum(sorted_eids, E_loc - 1)]
    keep = (rank < C) & (sorted_eids < E_loc)
    slot = jnp.where(keep, rank, C).astype(jnp.int32)
    eid_safe = jnp.minimum(sorted_eids, E_loc - 1).astype(jnp.int32)
    tok = (order // k).astype(jnp.int32)

    disp = jnp.full((E_loc, C + 1), T, jnp.int32)
    disp = disp.at[eid_safe, slot].set(jnp.where(keep, tok, T))
    xpad = jnp.concatenate([xf, jnp.zeros((1, D), xf.dtype)], axis=0)
    xe = xpad[disp]                                      # (E_loc, C+1, D)
    xe = shard(xe, P("model", "data", None))

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wi_gate)) \
        * jnp.einsum("ecd,edf->ecf", xe, wi_up)
    h = shard(h, P("model", "data", None))
    ye = jnp.einsum("ecf,efd->ecd", h, wo)               # (E_loc, C+1, D)
    ye = shard(ye, P("model", "data", None))

    rows = ye[eid_safe, slot]                            # (Tk, D)
    wsorted = (w.reshape(Tk)[order] * keep).astype(rows.dtype)
    return jax.ops.segment_sum(rows * wsorted[:, None], tok, num_segments=T)


class ExpertTouchTracker:
    """Aggregates which experts the router selected since the last
    snapshot flight (the dirty-delta saving path's provider signal).

    Disabled by default (zero overhead: the debug callback is only
    staged into the jaxpr when `enable()` ran before tracing).  The
    router feeds every `sel` through `record`; the snapshot driver calls
    `consume()` at flight time for the touched mask and resets it.
    """

    def __init__(self):
        self._lock = named_lock("moe.touched")
        self._mask: np.ndarray = np.zeros(0, bool)
        self.enabled = False

    def enable(self, num_experts: int) -> "ExpertTouchTracker":
        with self._lock:
            self._mask = np.zeros(int(num_experts), bool)
            self.enabled = True
        return self

    def disable(self) -> None:
        with self._lock:
            self.enabled = False
            self._mask = np.zeros(0, bool)

    def record(self, sel) -> None:
        """Fold a (T, k) routed-expert id array into the mask (host
        side; also the target of the in-jit debug callback)."""
        with self._lock:
            if not self.enabled:
                return
            ids = np.asarray(sel).reshape(-1)
            ids = ids[(ids >= 0) & (ids < self._mask.size)]
            self._mask[np.unique(ids)] = True

    def consume(self) -> np.ndarray:
        """Return-and-reset the aggregated touched mask."""
        with self._lock:
            m = self._mask.copy()
            self._mask[:] = False
            return m

    def peek(self) -> np.ndarray:
        with self._lock:
            return self._mask.copy()


# module-level singleton: the router is pure-functional, so dirtiness
# aggregation has to live beside it rather than in model state
TOUCHED = ExpertTouchTracker()


def _route(p, cfg, xf):
    logits = (xf.astype(jnp.float32) @ p["router"])          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    w, sel = jax.lax.top_k(probs, cfg.experts_per_token)     # (T, k)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    if TOUCHED.enabled:
        jax.debug.callback(TOUCHED.record, sel)
    return probs, w, sel


def _aux_loss(cfg, probs, sel):
    """Switch-style load-balance auxiliary loss."""
    E = cfg.num_experts
    me = jnp.mean(probs, axis=0)                             # (E,)
    ce_frac = jnp.mean(
        (jax.nn.one_hot(sel, E, dtype=jnp.float32)).sum(1), axis=0)
    return E * jnp.sum(me * ce_frac) / cfg.experts_per_token


def moe_ffn_gspmd(p, cfg, x):
    """GSPMD-inferred dispatch (baseline). x: (B,S,D) -> (y, aux)."""
    B, S, D = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    T = B * S
    C = _capacity(T, k, E, cfg.capacity_factor)
    if cfg.moe_pad_capacity:
        # keep the (C+1)-slot dispatch buffer divisible by the data axis so
        # GSPMD can shard the capacity dim (otherwise expert compute is
        # only expert-parallel -> 16x undersharded on a 16x16 mesh)
        m = cfg.moe_pad_capacity
        C = -(-(C + 1) // m) * m - 1
    xf = x.reshape(T, D)
    probs, w, sel = _route(p, cfg, xf)
    y = _dispatch_compute(xf, probs, w, sel, p["wi_gate"], p["wi_up"],
                          p["wo"], C)
    y = shard(y.reshape(B, S, D), P(("data",), None, None))
    return y.astype(x.dtype), _aux_loss(cfg, probs, sel)


def moe_ffn_ep(p, cfg, x):
    """Explicit expert-parallel MoE (§Perf, beyond paper).

    shard_map over the full mesh: tokens stay sharded over (pod, data);
    expert weights are sharded over "model" (FSDP shards over "data" are
    all-gathered locally, textbook FSDP); each device runs the *local*
    capacity-gather dispatch for its E/model_parallel experts on its own
    token shard, and partial outputs are psum'd over "model".  Collective
    traffic per layer is one all-gather of local expert weights plus one
    (T_local, D) psum — versus the TB-scale all-reduces GSPMD infers for
    the data-dependent gathers of the baseline.
    """
    from repro.dist.api import _active_mesh, adapt_spec
    mesh = _active_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return moe_ffn_gspmd(p, cfg, x)

    B, S, D = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    ep = sizes["model"] if E % sizes["model"] == 0 else 1
    if ep == 1:
        return moe_ffn_gspmd(p, cfg, x)
    dp_axes = tuple(a for a in ("pod", "data") if a in sizes)
    dp = math.prod(sizes[a] for a in dp_axes) if dp_axes else 1
    if (B * S) % dp:
        return moe_ffn_gspmd(p, cfg, x)
    T_loc = B * S // dp
    C_loc = _capacity(T_loc, k, E, cfg.capacity_factor)
    fsdp = tuple(a for a in ("pod", "data") if a in sizes) if cfg.fsdp \
        else ()

    def local_fn(xl, router, wg, wu, wo):
        # xl: (B_loc, S, D); wg/wu/wo: local expert shards
        if fsdp:
            wg_f = jax.lax.all_gather(wg, fsdp, axis=1, tiled=True)
            wu_f = jax.lax.all_gather(wu, fsdp, axis=1, tiled=True)
            wo_f = jax.lax.all_gather(wo, fsdp, axis=1, tiled=True)
        else:
            wg_f, wu_f, wo_f = wg, wu, wo
        E_loc = wg_f.shape[0]
        bl, sl, _ = xl.shape
        xf = xl.reshape(bl * sl, D)
        probs, w, sel = _route({"router": router}, cfg, xf)
        m_idx = jax.lax.axis_index("model")
        sel_loc = jnp.where(sel // E_loc == m_idx, sel % E_loc, E_loc)
        y = _dispatch_compute(xf, probs, w, sel_loc, wg_f, wu_f, wo_f,
                              C_loc)
        y = jax.lax.psum(y, "model")
        aux = _aux_loss(cfg, probs, sel)
        if dp_axes:
            aux = jax.lax.pmean(aux, dp_axes)
        return y.reshape(bl, sl, D).astype(xl.dtype), aux

    x_spec = P(dp_axes if dp_axes else None, None, None)
    w_spec = P("model", fsdp if fsdp else None, None)
    y, aux = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(x_spec, P(None, None), w_spec, w_spec, w_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, p["router"], p["wi_gate"], p["wi_up"], p["wo"])
    return y, aux


def moe_ffn(p, cfg, x):
    """x: (B, S, D) -> (B, S, D), plus router aux loss."""
    if cfg.moe_ep:
        return moe_ffn_ep(p, cfg, x)
    return moe_ffn_gspmd(p, cfg, x)
