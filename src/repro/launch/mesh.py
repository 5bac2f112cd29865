"""Production mesh construction (task spec: MULTI-POD DRY-RUN item 1).

Defined as FUNCTIONS so importing this module never touches jax device
state; ``jax.make_mesh`` is only called when a launcher actually runs.

Topology: TPU v5e, 256 chips/pod as a (16, 16) = (data, model) grid;
multi-pod adds the leading "pod" axis (2 pods = 512 chips) used for
data parallelism across the DCN/ICI pod boundary.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axis types (sharding propagated by the
    compiler, as the models' sharding rules expect)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(n: int | None = None, axis: str = "data"):
    """1-D mesh over however many (host) devices exist — used by the
    distributed-spMVM examples and tests."""
    n = n or len(jax.devices())
    return make_mesh((n,), (axis,))
