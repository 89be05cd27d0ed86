"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before any jax
initialisation; smoke tests must keep seeing 1 device).
"""
from __future__ import annotations

import jax

from repro.sharding.context import MeshContext


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with every axis ``Auto``: the models place
    activations with ``with_sharding_constraint``, which jax refuses on
    the ``Explicit`` axes ``make_mesh`` defaults to."""
    from jax.sharding import AxisType
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_context(*, multi_pod: bool = False) -> MeshContext:
    mesh = make_production_mesh(multi_pod=multi_pod)
    dp = ("pod", "data") if multi_pod else ("data",)
    return MeshContext(mesh=mesh, dp_axes=dp, tp_axis="model")


def make_host_mesh(n_devices: int | None = None,
                   model: int = 1) -> MeshContext:
    """Small mesh over whatever devices exist (tests/examples)."""
    n = n_devices or len(jax.devices())
    assert n % model == 0
    mesh = make_mesh((n // model, model), ("data", "model"))
    return MeshContext(mesh=mesh, dp_axes=("data",), tp_axis="model")
