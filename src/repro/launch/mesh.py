"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module does not touch jax device state.  The dry-run entrypoint sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 BEFORE importing jax;
everything else sees the real (single) device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto(n: int) -> tuple:
    # jax.make_mesh defaults to Explicit axes, under which the seed
    # engine's padded-batch slice and the model layer's sharding rules
    # raise ShardingTypeError; every mesh here is Auto
    return (AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model).
    Multi-pod: 2 pods x 256 = 512 chips (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_mesh(shape: tuple, axes: tuple):
    """Arbitrary mesh (tests / elastic rescale)."""
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_seed_mesh(num_devices: int = None):
    """1-D "seeds" mesh for sharding a simulation-seed batch axis
    (``cluster.state.batched_rollout(devices=N)``).

    ``None`` takes every device the runtime exposes.  Asking for more
    than that raises: a silent smaller mesh would report a 4-chip run
    that used one.  On CPU, launch with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set BEFORE
    importing jax, same rule as the dry-run entrypoint) to get N devices.
    """
    avail = jax.device_count()
    n = avail if num_devices is None else num_devices
    if not 1 <= n <= avail:
        raise ValueError(f"a {n}-device seed mesh was requested, but the "
                         f"{jax.default_backend()} runtime exposes {avail}")
    return jax.make_mesh((n,), ("seeds",), axis_types=_auto(1))


def data_axes(mesh) -> tuple:
    """All non-model axes act as the combined data/FSDP domain."""
    return tuple(a for a in mesh.axis_names if a != "model")
