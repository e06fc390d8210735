"""MoE dispatch vs a per-expert python-loop oracle, including the capacity
drop rule (tokens sorted stably by expert; first C per expert kept)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
import dataclasses

from repro.models.moe import _capacity, init_moe, moe_ffn


def oracle(p, cfg, x):
    """Straightforward python/numpy reimplementation."""
    B, S, D = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    T = B * S
    C = _capacity(T, k, E, cfg.capacity_factor)
    xf = np.asarray(x, np.float32).reshape(T, D)
    logits = xf @ np.asarray(p["router"], np.float32)
    ex = np.exp(logits - logits.max(-1, keepdims=True))
    probs = ex / ex.sum(-1, keepdims=True)
    order = np.argsort(-probs, axis=-1, kind="stable")
    sel = order[:, :k]
    w = np.take_along_axis(probs, sel, axis=-1)
    w = w / w.sum(-1, keepdims=True)

    # stable sort of (token,slot) pairs by expert -> rank within expert
    eids = sel.reshape(-1)
    sort_order = np.argsort(eids, kind="stable")
    rank = np.zeros(T * k, np.int64)
    counts = {}
    for pos in sort_order:
        e = eids[pos]
        rank[pos] = counts.get(e, 0)
        counts[e] = rank[pos] + 1

    y = np.zeros((T, D), np.float32)
    wg = np.asarray(p["wi_gate"], np.float32)
    wu = np.asarray(p["wi_up"], np.float32)
    wo = np.asarray(p["wo"], np.float32)
    for t in range(T):
        for j in range(k):
            flat = t * k + j
            e = sel[t, j]
            if rank[flat] >= C:
                continue                      # dropped
            h = xf[t] @ wg[e]
            h = h / (1 + np.exp(-h)) * (xf[t] @ wu[e])
            y[t] += w[t, j] * (h @ wo[e])
    return y.reshape(B, S, D)


@pytest.mark.parametrize("cf", [8.0, 0.5])   # drop-free and heavy-drop
def test_moe_matches_oracle(cf):
    base = get_config("dbrx-132b").reduced()
    cfg = dataclasses.replace(base, capacity_factor=cf)
    p = init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 6, cfg.d_model))
    y, aux, _ = moe_ffn(p, cfg, x)
    ref = oracle(p, cfg, x)
    np.testing.assert_allclose(np.asarray(y, np.float32), ref,
                               atol=2e-4, rtol=1e-3)
    assert float(aux) > 0


def test_moe_top1_and_many_experts():
    base = get_config("kimi-k2-1t-a32b").reduced()
    cfg = dataclasses.replace(base, num_experts=4, experts_per_token=1,
                              capacity_factor=8.0)
    p = init_moe(jax.random.PRNGKey(3), cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 8, cfg.d_model))
    y, _, _ = moe_ffn(p, cfg, x)
    ref = oracle(p, cfg, x)
    np.testing.assert_allclose(np.asarray(y, np.float32), ref,
                               atol=2e-4, rtol=1e-3)


def test_moe_grads_flow_through_router():
    cfg = get_config("dbrx-132b").reduced()
    p = init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 4, cfg.d_model))

    def loss(p):
        y, aux, _ = moe_ffn(p, cfg, x)
        return jnp.sum(y ** 2) + 0.01 * aux

    g = jax.grad(loss)(p)
    assert float(jnp.max(jnp.abs(g["router"]))) > 0
    assert float(jnp.max(jnp.abs(g["wi_gate"]))) > 0
