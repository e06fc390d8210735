"""REFT-JAX: reliable & efficient in-memory fault tolerance for
hybrid-parallel training — production-grade JAX reproduction.

Subpackages: api (unified checkpointing facade), core (the paper), models,
configs, optim, data, dist, ckpt, kernels (Pallas TPU), launch, plus, at
the repo root, tests/, bench/ (the on-chip training benchmark),
benchmarks/ (restore and object-store drills), examples/ and
`chip_smoke.py`, the one-chip end-to-end check.  See docs/API.md and
ROADMAP.md.
"""
__version__ = "1.0.0"
