"""Production mesh construction (TPU v5e pods; host-device placeholders in
the dry-run).

A function, not a module constant: importing this module never touches jax
device state (the dry-run must set XLA_FLAGS before any device query).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the models place
    activations through GSPMD sharding constraints (``repro.sharding``),
    which JAX's default ``Explicit`` axes reject."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """(16, 16) data×model single pod; (2, 16, 16) pod×data×model for 2 pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever this host offers, as a trivial (1, N) mesh — used by smoke
    tests that exercise the sharded code path on CPU."""
    n = len(jax.devices())
    return make_mesh((1, n), ("data", "model"))


def chips(mesh) -> int:
    return mesh.devices.size
