"""Production meshes. A FUNCTION (not a module-level constant) so importing
this module never touches jax device state.

Every mesh here has Auto axes (the compiler propagates shardings): the
sharding policy and step builders are written for that, and
``jax.make_mesh`` alone would make the axes Explicit.
"""
from __future__ import annotations

import jax


def _auto(axes) -> tuple:
    return (jax.sharding.AxisType.Auto,) * len(axes)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(axes))


def make_host_mesh(model_axis: int = 1, data_axis: int | None = None):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    data_axis = data_axis or (n // model_axis)
    return jax.make_mesh((data_axis, model_axis), ("data", "model"),
                         axis_types=_auto("dm"))


# --------------------------------------------------- scan-shard placement --
def host_device_count() -> int:
    """Devices visible to this process. On CPU CI this is 1 unless
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` was set
    before the first jax import (tests/conftest.py does)."""
    return len(jax.devices())


def shard_devices(n_shards: int | None = None) -> list:
    """Device placement for the sharded scan engine (DESIGN.md §9): one
    device per shard executor, round-robin when shards outnumber
    devices. The pmap lockstep path only uses the leading
    ``min(n_shards, device_count)`` distinct devices; the round-robin
    tail is for callers that drive shards individually."""
    devs = jax.devices()
    if n_shards is None:
        n_shards = len(devs)
    return [devs[i % len(devs)] for i in range(n_shards)]
