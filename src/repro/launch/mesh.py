"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — required because the dry-run must set
XLA_FLAGS before the first jax call, and smoke tests must see 1 device.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_test_mesh", "axis_sizes"]


def make_production_mesh(*, multi_pod: bool = False, shape=None):
    """(16, 16) single pod = 256 chips; (2, 16, 16) = 2 pods × 256 chips.

    Axes: ``pod`` crosses DCN (pure DP, params replicated per pod);
    ``data`` is FSDP/DP inside the pod; ``model`` is tensor/expert parallel.

    ``shape`` overrides the (data, model) factorization of the same 256
    chips per pod — e.g. (64, 4) for architectures whose head structure only
    shards 4-way (xLSTM; §Perf iteration B2).
    """
    if shape is None:
        shape = (2, 16, 16) if multi_pod else (16, 16)
        axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    else:
        shape = tuple(shape)
        axes = ("pod", "data", "model") if len(shape) == 3 else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CPU integration tests (requires forced host devices)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
