"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — required because the dry-run must set
XLA_FLAGS before the first jax call, and tests must see 1 CPU device.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto(n: int):
    """Auto (not Explicit) axes: the model code relies on GSPMD propagation
    (sharding rules + constraints), which Explicit axes reject."""
    return (AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single-pod (256 chips) or 2×16×16 two-pod (512 chips) mesh.

    Axes: ``data`` (DP / FSDP), ``model`` (TP / EP); multi-pod prepends
    ``pod`` (hierarchical DP by default, reassignable to pipeline stages).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_test_mesh(n_data: int = 2, n_model: int = 2, pods: int = 0):
    """Small mesh for CI-scale distribution tests (host devices)."""
    if pods:
        return jax.make_mesh((pods, n_data, n_model),
                             ("pod", "data", "model"), axis_types=_auto(3))
    return jax.make_mesh((n_data, n_model), ("data", "model"),
                         axis_types=_auto(2))
