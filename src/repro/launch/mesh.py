"""Production mesh builders (TPU v5e pods; host-device placeholders on CPU).

FUNCTIONS, not module-level constants — importing this module must not
touch jax device state.  Every mesh here uses ``AxisType.Auto`` axes (the
GSPMD-propagated sharding the partition rules in ``repro.sharding`` are
written for); ``jax.make_mesh`` alone defaults to explicit axes.  Make a
mesh ambient with ``jax.sharding.set_mesh(mesh)``.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType


def make_auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...], *,
                   devices: Optional[Sequence] = None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 2):
    """Small mesh for CPU tests (requires >= data*model host devices)."""
    return make_auto_mesh((data, model), ("data", "model"))


def make_client_mesh(num_devices: Optional[int] = None):
    """1-D ("clients",) mesh for the SFL round engine: the K-client axis of
    the stacked adapters/batches shards across these devices (K must be a
    multiple of the device count).  Defaults to every visible device."""
    devs = jax.devices()
    n = num_devices or len(devs)
    return make_auto_mesh((n,), ("clients",), devices=devs[:n])
