"""Production mesh builders (functions, not module constants — importing this
module never touches jax device state)."""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    # Auto axes: the sharding rules are hints the compiler propagates
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single pod (256 chips) or 2×16×16 multi-pod (512 chips).

    Axes: 'pod' (DCI-connected pods), 'data' (DP/FSDP), 'model' (TP).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Degenerate mesh over however many devices exist (CPU tests/examples)."""
    n = len(jax.devices())
    return _make_mesh((n // model, model), ("data", "model"))
