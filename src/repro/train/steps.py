"""Train / prefill / decode step factories.

The train state is the exact pytree REFT snapshots: params + optimizer
moments + step + data-RNG key (the paper's "model parameters, optimizer
states, and RNG states").
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import model as M
from repro.optim.adam import AdamConfig, adam_init, adam_update


@dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: Any
    rng: Any

    def tree(self):
        return {"params": self.params, "opt_state": self.opt_state,
                "step": self.step, "rng": self.rng}

    @classmethod
    def from_tree(cls, t):
        return cls(params=t["params"], opt_state=t["opt_state"],
                   step=t["step"], rng=t["rng"])


def init_train_state(cfg: ModelConfig, seed: int = 0) -> TrainState:
    key = jax.random.PRNGKey(seed)
    params = M.init_params(cfg, key)
    return TrainState(params=params, opt_state=adam_init(params),
                      step=jnp.zeros((), jnp.int32),
                      rng=jax.random.PRNGKey(seed + 1))


def make_train_step(cfg: ModelConfig, opt: AdamConfig | None = None,
                    unroll: bool = False, microbatches: int = 1):
    """Train-step factory.

    microbatches > 1 splits the global batch on axis 0 and accumulates
    gradients over a lax.scan — the standard memory/throughput knob when
    the per-step activation footprint exceeds HBM (grads are averaged, so
    the update is identical to the full-batch step for equal-size chunks).
    """
    # per-call default: a signature-level AdamConfig() would be one shared
    # instance across every factory call (the PR 1 aliased-config bug)
    opt = opt if opt is not None else AdamConfig()

    def loss_fn(p, batch):
        loss, out = M.forward(cfg, p, batch, unroll=unroll)
        # the step's counters: the MoE load, where the config has experts
        return loss, {k: jax.lax.stop_gradient(v) for k, v in out.items()
                      if k.startswith("moe_")}

    def full_grads(params, batch):
        (loss, stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch)
        return loss, grads, stats

    def accum_grads(params, batch):
        def split(x):
            b = x.shape[0]
            assert b % microbatches == 0, (b, microbatches)
            return x.reshape(microbatches, b // microbatches, *x.shape[1:])
        mb = jax.tree.map(split, batch)

        def body(carry, b_i):
            loss_acc, g_acc, st_acc = carry
            (loss_i, st_i), g_i = jax.value_and_grad(
                loss_fn, has_aux=True)(params, b_i)
            g_acc = jax.tree.map(jnp.add, g_acc, g_i)
            return (loss_acc + loss_i, g_acc,
                    M.merge_stats(st_acc, st_i)), None

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             params)
        st0 = {k: v for k, v in M._acc0(cfg).items() if k.startswith("moe_")}
        (loss, grads, stats), _ = jax.lax.scan(
            body, (jnp.zeros(()), zeros, st0), mb)
        inv = 1.0 / microbatches
        return loss * inv, jax.tree.map(lambda g: g * inv, grads), stats

    def train_step(state: dict, batch: dict) -> tuple:
        loss, grads, stats = (full_grads if microbatches == 1
                              else accum_grads)(state["params"], batch)
        with jax.named_scope("optimizer"):
            new_params, new_opt, gnorm = adam_update(
                opt, grads, state["opt_state"], state["params"])
        new_state = {
            "params": new_params,
            "opt_state": new_opt,
            "step": state["step"] + 1,
            "rng": jax.random.fold_in(state["rng"], state["step"]),
        }
        return new_state, {"loss": loss, "grad_norm": gnorm, **stats}

    return train_step


def with_step_boundary(step_fn: Callable,
                       notify: Callable[[], None] = None) -> Callable:
    """Yield hook for the HASC saving pipeline: wrap an (already-jitted)
    step function so every invocation ticks the snapshot pipeline's
    step-boundary gate — in-flight L1 device pumps then schedule their
    bucket bursts at step boundaries instead of racing the step for host
    bandwidth.  Wrap OUTSIDE `jax.jit` (the tick is a Python-side effect;
    under a trace it would fire once at trace time and never again):

        step_fn = with_step_boundary(jax.jit(make_train_step(cfg)))
    """
    if notify is None:
        from repro.core.pipeline import step_boundary as notify

    @functools.wraps(step_fn)
    def stepped(*args, **kw):
        out = step_fn(*args, **kw)
        notify()
        return out
    return stepped


def make_eval_step(cfg: ModelConfig):
    def eval_step(params, batch):
        loss, _ = M.forward(cfg, params, batch, remat=False)
        return loss
    return eval_step


def make_prefill_step(cfg: ModelConfig, unroll: bool = False):
    def prefill_step(params, batch):
        logits, caches = M.logits_fn(cfg, params, batch, unroll=unroll)
        return logits, caches
    return prefill_step


def make_decode_step(cfg: ModelConfig, unroll: bool = False):
    def serve_step(params, cache, tokens):
        logits, new_cache = M.decode_step(cfg, params, cache, tokens,
                                          unroll=unroll)
        return logits, new_cache
    return serve_step
