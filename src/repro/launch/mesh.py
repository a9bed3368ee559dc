"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state; the dry-run's ``main()`` sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before JAX
initializes a backend, to fabricate placeholder devices.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh


def _axis_kwargs(n_axes: int) -> dict:
    return {"axis_types": (AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_axis_kwargs(len(axes)))


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    return jax.make_mesh(shape, axes, **_axis_kwargs(len(axes)))


def make_submesh(n_chips: int, *, model_parallel: Optional[int] = None
                 ) -> Mesh:
    """A thin-instance sub-mesh of ``n_chips`` chips: (data', model').

    Packrat's ⟨i,t,b⟩ instances are SPMD-identical, so profiling lowers
    one representative instance on a t-chip sub-mesh (DESIGN.md §5).
    ``model_parallel`` defaults to all chips (pure TP thin instance).
    """
    tp = model_parallel or n_chips
    if n_chips % tp:
        raise ValueError(f"{tp=} must divide {n_chips=}")
    dp = n_chips // tp
    return jax.make_mesh((dp, tp), ("data", "model"), **_axis_kwargs(2))
