"""Production mesh builders.

``make_production_mesh`` is a function (never a module-level constant) so
importing this module touches no jax device state — required because the
dry-run must set XLA_FLAGS before any jax initialisation.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the steps place arrays with
    ``jit`` shardings and ``with_sharding_constraint``, which only accept
    Auto axes (``make_mesh`` defaults to Explicit ones)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips when ``multi_pod``."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_test_mesh(*, multi_pod: bool = False):
    """Small mesh for CI (8 host devices): 2x2(x2)."""
    shape = (2, 2, 2) if multi_pod else (2, 4)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def parse_mesh(spec: str):
    """Mesh from a CLI spec: "DxM" -> (data, model), "PxDxM" -> (pod, data,
    model).  "1x1" is the single-device degenerate mesh."""
    dims = tuple(int(d) for d in spec.lower().split("x"))
    if len(dims) == 2:
        return _mesh(dims, ("data", "model"))
    if len(dims) == 3:
        return _mesh(dims, ("pod", "data", "model"))
    raise ValueError(f"mesh spec must be DxM or PxDxM, got {spec!r}")


def data_axes(mesh) -> tuple:
    """Axes a global-batch dimension shards over (pod folds into data).

    Delegates to :func:`repro.dist.sharding.data_axes` — the single source
    of truth, which also drops size-1 axes (naming them trips an XLA
    IsManualSubgroup abort near manual pod subgroups).  Imported lazily so
    importing this module still touches no jax device state.
    """
    from repro.dist.sharding import data_axes as _data_axes

    return _data_axes(mesh)
