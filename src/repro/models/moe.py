"""Top-k mixture-of-experts: routing over every expert, and the part of
the result that the experts held here give.

An expert layer is told which experts it holds (`_local_moe`): on one chip
its share (`experts_held`, from expert 0), under expert parallelism the
slice of the "model" mesh axis.  Its tokens are routed over all experts;
pairs routed to experts held elsewhere add nothing here.  Two dispatches,
chosen by the config:

* capacity (`capacity_factor` > 0): tokens sorted by expert (stable),
  ranked within each expert and gathered into an (E, C+1, D) buffer (slot
  C absorbs the overflow; dropped tokens contribute zero via a masked
  combine weight).  The expert einsums carry sharding constraints so the E
  axis maps onto the "model" (expert-parallel) mesh axis and the capacity
  axis onto "data".
* dropless (`capacity_factor` 0): pairs sorted by expert, the held ones
  first, and grouped matrix products over the held experts' rows only
  (on a TPU megablox's Pallas `gmm`, elsewhere `jax.lax.ragged_dot`).

Correctness is checked against per-expert python-loop oracles in tests
(including the drop rule).
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
    D, F = cfg.d_model, cfg.d_ff
    E, E_held = cfg.num_experts, cfg.num_experts_held
    pd = pdtype_of(cfg)
    ks = jax.random.split(key, 4)
    return {
        "router": dense_init(ks[0], (D, E), jnp.float32),
        "wi_gate": dense_init(ks[1], (E_held, D, F), pd),
        "wi_up": dense_init(ks[2], (E_held, D, F), pd),
        "wo": dense_init(ks[3], (E_held, F, D), pd),
    }


def _capacity(T, k, E, factor):
    return max(1, int(math.ceil(T * k / E * factor)))


def _dispatch_compute(xf, w, sel, wi_gate, wi_up, wo, C):
    """Capacity-gather dispatch + expert einsums + weighted combine.

    xf: (T, D); w/sel: (T, k) routing weights / local expert ids (id
    E_loc = wi_gate.shape[0] marks a pair routed elsewhere, which is
    masked out).  Returns (T, D).
    """
    T, D = xf.shape
    E_loc = wi_gate.shape[0]
    k = sel.shape[1]
    Tk = T * k

    with jax.named_scope("moe.dispatch"):
        eids = sel.reshape(Tk)
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
        xe = xpad[disp]                                  # (E_loc, C+1, D)
        xe = shard(xe, P("model", "data", None))

    with jax.named_scope("moe.experts"):
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wi_gate)) \
            * jnp.einsum("ecd,edf->ecf", xe, wi_up)
        h = shard(h, P("model", "data", None))
        ye = jnp.einsum("ecf,efd->ecd", h, wo)           # (E_loc, C+1, D)
        ye = shard(ye, P("model", "data", None))

    with jax.named_scope("moe.combine"):
        rows = ye[eid_safe, slot]                        # (Tk, D)
        wsorted = (w.reshape(Tk)[order] * keep).astype(rows.dtype)
        return jax.ops.segment_sum(rows * wsorted[:, None], tok,
                                   num_segments=T)


# ------------------------------------------------------------ dropless
@jax.custom_vjp
def _permute_rows(x, perm, inv):
    """x[perm] for a permutation `perm` (inverse `inv`): the gradient is a
    gather by the inverse, not a scatter-add."""
    return x[perm]


def _permute_rows_fwd(x, perm, inv):
    return x[perm], inv


def _permute_rows_bwd(inv, g):
    return g[inv], None, None


_permute_rows.defvjp(_permute_rows_fwd, _permute_rows_bwd)


@jax.custom_vjp
def _repeat_rows(xf, pair, inv):
    """xf[pair // k] for a permutation `pair` of the T*k (token, slot)
    pairs (inverse `inv`): each token's row once per pair.  The gradient
    sums each token's k pairs through the inverse (a gather, not a
    scatter-add)."""
    return xf[pair // (pair.shape[0] // xf.shape[0])]


def _repeat_rows_fwd(xf, pair, inv):
    return xf[pair // (pair.shape[0] // xf.shape[0])], (inv, xf.shape[0])


def _repeat_rows_bwd(res, g):
    inv, T = res
    rows = g[inv].astype(jnp.float32).reshape(T, -1, g.shape[-1])
    return jnp.sum(rows, axis=1).astype(g.dtype), None, None


_repeat_rows.defvjp(_repeat_rows_fwd, _repeat_rows_bwd)


def _gmm_tiling(m, k, n):
    """megablox tiles (tm, tk, tn): 512 rows; k and n whole up to 1024,
    else the largest multiple of 128 under 1024 that divides them."""
    def edge(d, cap=1024):
        return next((b for b in range(min(d, cap) // 128 * 128, 0, -128)
                     if d % b == 0), d)
    return min(512, m), edge(k), edge(n)


def _grouped_tpu(x, w, sizes):
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    return gmm(x, w, sizes, x.dtype, _gmm_tiling)


def _grouped_default(x, w, sizes):
    return jax.lax.ragged_dot(x, w, sizes)


def _grouped(x, w, sizes):
    """Rows [sum(sizes[:e]), sum(sizes[:e+1])) of x times w[e]; rows past
    sum(sizes) are not computed (their values are left undefined)."""
    return jax.lax.platform_dependent(x, w, sizes, tpu=_grouped_tpu,
                                      default=_grouped_default)


def _dropless(xf, w, sel, sizes, wi_gate, wi_up, wo):
    """Every pair routed to a held expert, none dropped.  xf: (T, D); w/sel:
    (T, k) routing weights / local expert ids (E_loc: held elsewhere);
    sizes: (E_loc,) rows per held expert.  The grouped products run over
    the held rows only; the gather and combine move all T*k pairs."""
    T, D = xf.shape
    Tk = T * sel.shape[1]
    with jax.named_scope("moe.dispatch"):
        pair = jnp.argsort(sel.reshape(Tk), stable=True).astype(jnp.int32)
        inv = jnp.zeros_like(pair).at[pair].set(
            jnp.arange(Tk, dtype=jnp.int32))
        here = (jnp.arange(Tk) < jnp.sum(sizes))[:, None]
        xs = jnp.where(here, _repeat_rows(xf, pair, inv), 0)
    with jax.named_scope("moe.experts"):
        h = jax.nn.silu(_grouped(xs, wi_gate, sizes)) \
            * _grouped(xs, wi_up, sizes)
        ys = jnp.where(here, _grouped(h.astype(xs.dtype), wo, sizes), 0)
    with jax.named_scope("moe.combine"):
        y = _permute_rows(ys, inv, pair).reshape(T, -1, D)
        return jnp.sum(y.astype(jnp.float32) * w[..., None], axis=1)


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
    """Softmax over every expert, the top k, renormalised.  The router
    is float32 and so is its product (`highest`: a default-precision f32
    product on a TPU rounds its operands to bf16 and flips near-tied
    top-k choices)."""
    logits = jnp.dot(xf.astype(jnp.float32), p["router"],
                     precision=jax.lax.Precision.HIGHEST)    # (T, E)
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


def _local_moe(cfg, xf, router, wg, wu, wo, offset, C):
    """This chip's part of an expert layer.  Routes the tokens xf (T, D)
    over all experts and returns the part that its held experts
    [offset, offset + E_loc) give (T, D), the aux loss (the same on every
    share), and the share's load: `moe_rows_here` (pairs routed to a held
    expert) and `moe_max_load` (the busiest held expert's rows over the
    held mean).  C: the capacity per expert, or None for dropless."""
    E_loc = wg.shape[0]
    with jax.named_scope("moe.route"):
        probs, w, sel = _route({"router": router}, cfg, xf)
        loc = sel - offset
        loc = jnp.where((loc >= 0) & (loc < E_loc), loc, E_loc)
        sizes = jnp.sum(jax.nn.one_hot(loc, E_loc, dtype=jnp.int32),
                        axis=(0, 1))
    if C is None:
        y = _dropless(xf, w, loc, sizes, wg, wu, wo)
    else:
        y = _dispatch_compute(xf, w, loc, wg, wu, wo, C)
    rows = jnp.sum(sizes)
    stats = {"moe_rows_here": rows,
             "moe_max_load": jnp.max(sizes) * E_loc
             / jnp.maximum(rows, 1).astype(jnp.float32)}
    return y, _aux_loss(cfg, probs, sel), stats


def moe_ffn_gspmd(p, cfg, x):
    """One device's experts, or GSPMD-inferred sharding of them (baseline).
    x: (B,S,D) -> (y, aux, stats)."""
    B, S, D = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    T = B * S
    C = None
    if cfg.capacity_factor:
        C = _capacity(T, k, E, cfg.capacity_factor)
        if cfg.moe_pad_capacity:
            # keep the (C+1)-slot dispatch buffer divisible by the data
            # axis so GSPMD can shard the capacity dim (otherwise expert
            # compute is only expert-parallel -> 16x undersharded on a
            # 16x16 mesh)
            m = cfg.moe_pad_capacity
            C = -(-(C + 1) // m) * m - 1
    y, aux, stats = _local_moe(cfg, x.reshape(T, D), p["router"],
                               p["wi_gate"], p["wi_up"], p["wo"], 0, C)
    y = shard(y.reshape(B, S, D), P(("data",), None, None))
    return y.astype(x.dtype), aux, stats


def moe_ffn_ep(p, cfg, x):
    """Explicit expert-parallel MoE (§Perf, beyond paper).

    shard_map over the full mesh: tokens stay sharded over (pod, data);
    expert weights are sharded over "model" (FSDP shards over "data" are
    all-gathered locally, textbook FSDP); each device runs `_local_moe`
    for its E/model_parallel experts on its own token shard, and partial
    outputs are psum'd over "model".  Collective traffic per layer is one
    all-gather of local expert weights plus one (T_local, D) psum — versus
    the TB-scale all-reduces GSPMD infers for the data-dependent gathers
    of the baseline.  The load counters are the mean device's rows and
    the busiest device's expert.
    """
    from repro.dist.api import _active_mesh
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
    C_loc = (_capacity(T_loc, k, E, cfg.capacity_factor)
             if cfg.capacity_factor else None)
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
        bl, sl, _ = xl.shape
        offset = jax.lax.axis_index("model") * wg_f.shape[0]
        y, aux, st = _local_moe(cfg, xl.reshape(bl * sl, D), router, wg_f,
                                wu_f, wo_f, offset, C_loc)
        y = jax.lax.psum(y, "model")
        if dp_axes:
            aux = jax.lax.pmean(aux, dp_axes)
        axes = tuple(mesh.axis_names)
        st = {"moe_rows_here": jax.lax.psum(st["moe_rows_here"], axes)
              // mesh.size,
              "moe_max_load": jax.lax.pmax(st["moe_max_load"], axes)}
        return y.reshape(bl, sl, D).astype(xl.dtype), aux, st

    x_spec = P(dp_axes if dp_axes else None, None, None)
    w_spec = P("model", fsdp if fsdp else None, None)
    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(x_spec, P(None, None), w_spec, w_spec, w_spec),
        out_specs=(x_spec, P(), P()),
        check_vma=False,
    )(x, p["router"], p["wi_gate"], p["wi_up"], p["wo"])


def moe_ffn(p, cfg, x):
    """x: (B, S, D) -> (B, S, D), the router's aux loss, and the load
    counters (`_local_moe`)."""
    if cfg.moe_ep:
        return moe_ffn_ep(p, cfg, x)
    return moe_ffn_gspmd(p, cfg, x)
