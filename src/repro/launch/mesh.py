"""Production meshes. Functions, not module constants — importing this module
never touches jax device state (required for the dry-run's XLA_FLAGS dance).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_shape(shape, axes)


def make_mesh_shape(shape, axes):
    """Arbitrary mesh (tests, PP experiments), every axis explicit-Auto."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(n_data: int | None = 1, n_model: int = 1):
    """Small mesh over however many (host/CPU) devices exist.

    ``n_data=None`` auto-sizes the data axis to all host devices (divided
    by ``n_model``) — what StreamRuntime defaults to. Requesting more
    devices than exist raises a ValueError naming both counts.
    """
    n = len(jax.devices())
    if n_data is None:
        n_data = max(1, n // n_model)
    if n_data * n_model > n:
        raise ValueError(
            f"make_host_mesh: requested {n_data}×{n_model} = "
            f"{n_data * n_model} devices but only {n} host device(s) are "
            f"available; lower n_data/n_model or force more via "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=N")
    return make_mesh_shape((n_data, n_model), ("data", "model"))
