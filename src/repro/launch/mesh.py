"""Production mesh factory. A FUNCTION (not a module constant) so importing
this module never touches jax device state."""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    """`jax.make_mesh` with Auto axes: the engine's `shard_map` rounds and
    `NamedSharding` placements assume GSPMD propagation, and make_mesh
    defaults to Explicit axes."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """v5e-pod meshes: single pod 16x16 = 256 chips (data, model);
    multi-pod 2x16x16 = 512 chips (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(model: int = 1, data: int = 1, pod: int = 0):
    """Small mesh over however many (possibly fake) devices exist — used by
    the multi-device integration tests."""
    if pod:
        return _make_mesh((pod, data, model), ("pod", "data", "model"))
    return _make_mesh((data, model), ("data", "model"))
