"""Mesh-aware sharding primitives.

`shard(x, spec)` is the single annotation primitive the model code uses:
inside a mesh context it lowers to `with_sharding_constraint` after
adapting the spec to the axes the active mesh actually has; outside any
mesh (CPU smoke runs, the REFT training driver) it is the identity, so
the same model code runs everywhere.

`adapt_spec` implements the adaptation rules the dry-run relies on:
  * axis names the mesh does not have are dropped;
  * an axis (or tuple prefix) only survives if its cumulative size divides
    the corresponding array dimension — GSPMD requires even sharding.
"""
from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
from jax.sharding import NamedSharding, PartitionSpec as P


def _axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.axis_sizes))


def _active_mesh():
    """The mesh of the enclosing `jax.set_mesh` context, or None."""
    m = jax.sharding.get_abstract_mesh()
    return m if m.axis_names else None


def use_mesh(mesh):
    """Mesh context manager (`jax.set_mesh`)."""
    return jax.set_mesh(mesh)


def adapt_spec(spec: P, shape: Sequence[int], mesh) -> P:
    """Drop spec axes the mesh lacks or whose size does not divide the dim."""
    sizes = _axis_sizes(mesh)
    out = []
    for dim, entry in enumerate(spec):
        if entry is None:
            out.append(None)
            continue
        names = entry if isinstance(entry, tuple) else (entry,)
        kept, prod = [], 1
        for nm in names:
            if nm not in sizes:
                continue                     # axis not on this mesh
            if shape[dim] % (prod * sizes[nm]) != 0:
                break                        # longest dividing prefix only
            kept.append(nm)
            prod *= sizes[nm]
        if not kept:
            out.append(None)
        elif isinstance(entry, tuple):
            out.append(tuple(kept))
        else:
            out.append(kept[0])
    return P(*out)


def shard(x: Any, spec: P) -> Any:
    """Constrain `x` to `spec` on the active mesh (identity without one)."""
    mesh = _active_mesh()
    if mesh is None or jax.sharding.AxisType.Manual in mesh.axis_types:
        return x                     # no mesh, or inside shard_map: the
    sp = adapt_spec(spec, x.shape, mesh)   # body already holds shards
    if all(e is None for e in sp):
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, sp))
