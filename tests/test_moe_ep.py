"""Expert-parallel MoE (shard_map) vs the GSPMD baseline — bit-identical
outputs on a real multi-device mesh (8 forced CPU devices, subprocess so
the device-count flag can't leak into other tests)."""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, jax, jax.numpy as jnp
    from repro.configs import get_config
    from repro.models.moe import init_moe, moe_ffn_gspmd, moe_ffn_ep

    from repro.dist.api import use_mesh

    cfg = get_config("dbrx-132b").reduced()
    p = init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, cfg.d_model))
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    y_ref, _ = moe_ffn_gspmd(p, cfg, x)
    with use_mesh(mesh):
        y_ep, _ = jax.jit(lambda p, x: moe_ffn_ep(p, cfg, x))(p, x)
        cfg2 = dataclasses.replace(cfg, fsdp=True)
        y_fs, _ = jax.jit(lambda p, x: moe_ffn_ep(p, cfg2, x))(p, x)
    assert float(jnp.max(jnp.abs(y_ep - y_ref))) < 1e-5
    assert float(jnp.max(jnp.abs(y_fs - y_ref))) < 1e-5
    print("EP_OK")
""")


def test_moe_ep_matches_gspmd_on_8_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "EP_OK" in out.stdout, out.stderr[-2000:]


def test_moe_ep_falls_back_without_mesh():
    """No mesh context -> EP path silently equals the baseline."""
    import jax
    import jax.numpy as jnp
    import dataclasses
    from repro.configs import get_config
    from repro.models.moe import init_moe, moe_ffn

    cfg = dataclasses.replace(get_config("dbrx-132b").reduced(),
                              moe_ep=True)
    p = init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 4, cfg.d_model))
    y, aux = moe_ffn(p, cfg, x)
    assert y.shape == x.shape and bool(jnp.isfinite(aux))
