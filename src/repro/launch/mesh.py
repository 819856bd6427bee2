"""Mesh construction: every mesh of the repo is built here.

``jax.make_mesh`` gives ``Explicit`` axes by default, under which the
ring tier's gathers and scatters on sharded arrays raise
``ShardingTypeError``; ``make_mesh`` asks for ``Auto`` axes, which the
sharded DIGC state (DESIGN.md §10) is written for.

Defined as functions (never module-level constants) so importing this
module never touches jax device state — smoke tests must keep seeing
the single real CPU device."""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = 256 chips, axes (data, model).
    Multi-pod: (2, 16, 16) = 512 chips, axes (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    import math

    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devices)} — the "
            "dry-run entrypoint sets xla_force_host_platform_device_count"
        )
    return jax.make_mesh(
        tuple(shape), tuple(axes), devices=devices[:n],
        axis_types=(AxisType.Auto,) * len(axes),
    )


def describe(mesh) -> str:
    return f"mesh(shape={dict(zip(mesh.axis_names, mesh.devices.shape))}, devices={mesh.devices.size})"
