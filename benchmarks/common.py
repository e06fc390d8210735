"""Shared benchmark utilities."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.launch.cache import use_compile_cache

# every benchmark imports this module before it compiles anything
use_compile_cache()


def make_param_state(nbytes: int, seed: int = 0) -> dict:
    """Synthetic 'model + Adam moments' pytree of ~nbytes total."""
    n = max(1024, nbytes // 12)            # bf16 params + 2x fp32 moments
    k = jax.random.PRNGKey(seed)
    return {
        "params": jax.random.normal(k, (n,), jnp.bfloat16),
        "mu": jnp.zeros((n,), jnp.float32),
        "nu": jnp.zeros((n,), jnp.float32),
        "step": jnp.int32(0),
    }
