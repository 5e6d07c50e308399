"""Production mesh construction.

FUNCTIONS (not module-level constants) so importing this module never
touches jax device state — the dry-run must set XLA_FLAGS before first init.

Every mesh is built with ``Auto`` axis types: the model's sharding hints
(``distributed.constraints.constrain``) and the GSPMD-partitioned jits
expect the compiler to propagate shardings.  ``jax.make_mesh`` alone makes
``Explicit`` axes, under which those constraints and plain gathers are
refused.  Activate a mesh with ``jax.set_mesh(mesh)``.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / CPU examples)."""
    n = len(jax.devices())
    assert data * model <= n, f"need {data * model} devices, have {n}"
    return make_mesh((data, model), ("data", "model"),
                     devices=jax.devices()[: data * model])
