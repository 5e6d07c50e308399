"""Sharding-constraint helper usable from model code.

``constrain(x, spec)`` applies ``with_sharding_constraint`` against the
ambient mesh (the one the launcher activates with ``jax.set_mesh``); axis
names missing from the mesh are stripped, and with no mesh (single-device
tests) it is a no-op — so model code can express distribution *hints*
without depending on how it is launched.
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P


def _ambient_mesh():
    """The mesh set by ``jax.set_mesh`` (abstract inside a trace), or None."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def constrain(x, *spec):
    """Constrain ``x`` to PartitionSpec(*spec) on the ambient mesh; missing
    axes are stripped and axes that don't divide the dim are dropped, so the
    same model code is valid on any mesh (or none)."""
    mesh = _ambient_mesh()
    if mesh is None:
        return x
    names = set(mesh.axis_names)

    def axsize(a):
        return mesh.shape.get(a, 1)

    def keep(entry, dim):
        if entry is None:
            return None
        axes = entry if isinstance(entry, tuple) else (entry,)
        kept = tuple(a for a in axes if a in names)
        size = 1
        for a in kept:
            size *= axsize(a)
        if not kept or size == 0 or dim % size:
            return None
        return kept if len(kept) > 1 else kept[0]

    ndim = x.ndim
    entries = list(spec) + [None] * (ndim - len(spec))
    cleaned = P(*[keep(e, x.shape[i]) for i, e in enumerate(entries[:ndim])])
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, cleaned))
