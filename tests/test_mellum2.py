"""Mellum2-12B-A2.5B through the program, against the plain reference in
`mellum2_reference.py`, at small sizes on the CPU: the model's loss and
gradients, a held share of the experts, dropless dispatch under skewed
routing, YaRN RoPE, and the windowed splash kernel (interpreted)."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mellum2_reference as ref
from repro.configs import get_config
from repro.models import model as M
from repro.models import moe


def small(held=2, window=8, **kw):
    """The reduced config (one window + full period, 4 experts, top 2) with
    the window cut below the test's sequence length."""
    base = get_config("mellum2-12b-a2.5b").reduced()
    return dataclasses.replace(base, experts_held=held,
                               sliding_window=window, **kw)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)


@pytest.mark.parametrize("held, flash", [(2, False), (4, False), (2, True)])
def test_loss_and_grads_match_the_reference(monkeypatch, held, flash):
    """f32 program vs f32 reference: the same loss and gradient in every
    leaf, to f32 round-off (1e-4 relative; a wrong window, RoPE kind or
    routing weight moves them by 1e-2 or more).  `flash` lowers the
    threshold so the windowed and full layers run the pure-JAX loops."""
    import repro.models.attention as A
    if flash:
        monkeypatch.setattr(A, "FLASH_THRESHOLD", 16)
    cfg = small(held)
    assert M.static_windows(cfg) == [8, None]
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                             cfg.vocab_size)
    lab = jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0,
                             cfg.vocab_size)
    batch = {"tokens": tok, "labels": lab}

    def prog(p):
        return M.forward(cfg, p, batch)[0]
    lp, gp = jax.value_and_grad(prog)(params)
    lr, gr = jax.value_and_grad(lambda p: ref.loss(cfg, p, tok, lab))(params)
    np.testing.assert_allclose(float(lp), float(lr), rtol=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(gp)[0],
                            jax.tree.leaves(gr)):
        assert _rel(a, b) < 1e-4, (jax.tree_util.keystr(path), _rel(a, b))


@pytest.mark.parametrize("C", [None, 64], ids=["dropless", "capacity"])
def test_shares_add_up_to_the_uncut_layer(C):
    """Each chip of an expert-parallel layer holds E/E_held experts: the
    shares' outputs summed are the whole layer's (the reference over all
    experts), and every share computes the same load-balance loss."""
    cfg = small(held=4)
    p = moe.init_moe(jax.random.PRNGKey(3), cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, cfg.d_model))
    xf = x.reshape(-1, cfg.d_model)
    E_held = 2
    ys, auxes = [], []
    for off in range(0, cfg.num_experts, E_held):
        sl = slice(off, off + E_held)
        y, aux, st = moe._local_moe(cfg, xf, p["router"], p["wi_gate"][sl],
                                    p["wi_up"][sl], p["wo"][sl], off, C)
        ys.append(y)
        auxes.append(float(aux))
        assert int(st["moe_rows_here"]) > 0
    want, want_aux = ref.experts(cfg, p, xf)
    assert _rel(sum(ys), want) < 1e-5
    assert auxes == [auxes[0]] * len(auxes)
    np.testing.assert_allclose(auxes[0], float(want_aux), rtol=1e-6)


def test_dropless_keeps_every_pair_under_skewed_routing():
    """A router that sends most tokens to expert 0: the capacity dispatch
    (factor 1.25) drops pairs there, the dropless one computes them all,
    as the per-expert loop does, forward and backward."""
    cfg = small(held=4)
    p = moe.init_moe(jax.random.PRNGKey(5), cfg)
    p["router"] = p["router"].at[:, 0].add(0.1)
    x = 1.0 + jax.random.normal(jax.random.PRNGKey(6), (1, 64, cfg.d_model))
    xf = x.reshape(-1, cfg.d_model)
    T, k, E = xf.shape[0], cfg.experts_per_token, cfg.num_experts

    def run(C):
        def f(xf, p):
            y, _, st = moe._local_moe(cfg, xf, p["router"], p["wi_gate"],
                                      p["wi_up"], p["wo"], 0, C)
            return y, st
        return f
    (y, st) = run(None)(xf, p)
    want, _ = ref.experts(cfg, p, xf)
    assert _rel(y, want) < 1e-5
    assert int(st["moe_rows_here"]) == T * k
    assert float(st["moe_max_load"]) > 1.5          # skewed indeed
    y_cap, _ = run(moe._capacity(T, k, E, 1.25))(xf, p)
    assert float(jnp.max(jnp.abs(y_cap - want))) > 1e-2   # it drops

    ct = jax.random.normal(jax.random.PRNGKey(7), y.shape)
    g = jax.grad(lambda xf, p: jnp.sum(run(None)(xf, p)[0] * ct),
                 argnums=(0, 1))(xf, p)
    g_ref = jax.grad(lambda xf, p: jnp.sum(ref.experts(cfg, p, xf)[0] * ct),
                     argnums=(0, 1))(xf, p)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_ref)):
        assert _rel(a, b) < 1e-4


@pytest.mark.parametrize("hd", [64, 128])
def test_yarn_rope_angles_match_the_formula(hd):
    from repro.models.layers import rope_angles
    cfg = get_config("mellum2-12b-a2.5b")
    pos = jnp.arange(0, 20000, 97)
    cos, sin = rope_angles(pos, hd, cfg.rope_theta,
                           yarn_factor=cfg.yarn_factor,
                           original_max=cfg.yarn_original_max)
    f = ref.yarn_freqs(hd, cfg.rope_theta, cfg.yarn_factor,
                       cfg.yarn_original_max, 32.0, 1.0)
    ang = np.asarray(pos, np.float64)[:, None] * f
    af = 1.2772588722239782             # the published attention_factor
    np.testing.assert_allclose(np.asarray(cos), af * np.cos(ang), atol=2e-3)
    np.testing.assert_allclose(np.asarray(sin), af * np.sin(ang), atol=2e-3)
    # the fastest dimensions keep their frequency, the slowest are divided
    base = cfg.rope_theta ** (-np.arange(hd // 2) * 2.0 / hd)
    assert f[0] == pytest.approx(base[0])
    assert f[-1] == pytest.approx(base[-1] / cfg.yarn_factor)


def test_windowed_splash_matches_flash():
    """The splash kernel with a local mask (interpreted) against the
    pure-JAX loops with the same window, at Mellum2's G 8 and hd 128:
    output and q, k, v gradients within a few bf16 roundings.  S 2048 with
    a 256 window has wholly masked tiles, which the kernel skips."""
    from repro.kernels.causal_attention import causal_attention
    from repro.models.flash import flash_attention
    B, S, KV, G, hd, W = 1, 2048, 1, 8, 128, 256
    ks = jax.random.split(jax.random.PRNGKey(8), 4)
    q = jax.random.normal(ks[0], (B, S, KV, G, hd), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, KV, hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, KV, hd), jnp.bfloat16)
    ct = jax.random.normal(ks[3], (B, S, KV, G, hd), jnp.bfloat16)

    def kernel(q, k, v):
        return causal_attention(q, k, v, window=W, interpret=True)

    def loops(q, k, v):
        return flash_attention(q, k, v, window=W, block_q=256, block_k=256)

    def outs(f):
        o, vjp = jax.vjp(f, q, k, v)
        return (o, *vjp(ct))

    for name, a, r in zip(("out", "dq", "dk", "dv"), outs(kernel),
                          outs(loops)):
        a, r = np.asarray(a, np.float32), np.asarray(r, np.float32)
        tol = 4 * 2.0 ** -8 * np.abs(r).max()          # 4 bf16 ulps
        assert np.abs(a - r).max() <= tol, (name, np.abs(a - r).max(), tol)


def test_train_step_reports_the_moe_load():
    """The step's metrics carry the counters: pairs routed to the held
    experts over every layer, and the busiest held expert over the mean."""
    from repro.train.steps import init_train_state, make_train_step
    cfg = small(held=2)
    st = init_train_state(cfg, 0)
    tok = jax.random.randint(jax.random.PRNGKey(9), (2, 16), 0,
                             cfg.vocab_size)
    _, m = jax.jit(make_train_step(cfg))(st.tree(), {"tokens": tok,
                                                     "labels": tok})
    T, k = 2 * 16, cfg.experts_per_token
    rows = int(m["moe_rows_here"])
    assert 0 < rows <= cfg.num_layers * T * k
    assert float(m["moe_max_load"]) >= 1.0
