"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state.  Single pod:
16×16 = 256 chips ("data", "model").  Multi-pod: 2×16×16 = 512 chips
("pod", "data", "model") — the "pod" axis is pure data parallelism over
DCN and scales to N pods without code changes.
"""
from __future__ import annotations

import jax


def _auto_mesh(shape, axes) -> jax.sharding.Mesh:
    # Auto axes: the sharding plans place arrays through NamedShardings
    # and let the compiler propagate (jax.make_mesh defaults to Explicit)
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(model: int = 2, data: int = 2, pod: int = 0
                    ) -> jax.sharding.Mesh:
    """Small mesh over however many (possibly fake) devices exist — used by
    multi-device unit tests run with XLA_FLAGS host-device overrides."""
    if pod:
        return _auto_mesh((pod, data, model), ("pod", "data", "model"))
    return _auto_mesh((data, model), ("data", "model"))
