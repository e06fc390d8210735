"""Where a kernel runs: compiled for the accelerator that holds its input,
interpreted when that input lives on the CPU.

The choice is read from the array being processed, not from the process's
default backend: a host process that merely imports this package (the SMP,
a cluster node) never initialises a backend through it, and a CPU-resident
array in a TPU process is still interpreted rather than mis-compiled.
"""
from __future__ import annotations

import jax


def runs_interpreted(x) -> bool:
    """True iff a Pallas kernel over `x` has to run in interpret mode.

    A committed `jax.Array` answers from its own devices.  A numpy array
    or a tracer has no placement yet: it runs on the default device, so
    the default backend decides — the only case that consults it."""
    if isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer):
        return all(d.platform == "cpu" for d in x.devices())
    return jax.default_backend() == "cpu"
