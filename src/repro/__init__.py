"""REFT-JAX: reliable & efficient in-memory fault tolerance for
hybrid-parallel training — production-grade JAX reproduction.

Subpackages: api (unified checkpointing facade), core (the paper), models,
configs, optim, data, dist, ckpt, kernels (Pallas TPU), launch, plus
tests/ benchmarks/ examples/ at the repo root and `chip_smoke.py`, the
one-chip end-to-end check.  See docs/API.md and ROADMAP.md.
"""
__version__ = "1.0.0"
