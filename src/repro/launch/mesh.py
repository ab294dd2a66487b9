"""Production mesh construction (single-pod 16x16, multi-pod 2x16x16).

``make_production_mesh`` is a function (not a module-level constant) so
importing this module never touches jax device state — required because the
dry-run forces 512 host devices via XLA_FLAGS before first jax init, while
smoke tests must keep seeing 1 device.
"""
from __future__ import annotations

import math

import jax


def _make_mesh(shape, axes, devices):
    return jax.make_mesh(
        shape, axes, devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def require_devices(n: int, what: str):
    """The first ``n`` devices, or an error naming what was found."""
    devices = jax.devices()
    if len(devices) < n:
        raise ValueError(
            f"{what} needs {n} devices, found {len(devices)} on platform "
            f"{devices[0].platform!r} ({devices[0].device_kind})")
    return devices[:n]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    devices = require_devices(math.prod(shape), f"mesh {shape}")
    return _make_mesh(shape, axes, devices)


def make_host_mesh(model_parallel: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    dp = n // model_parallel
    return _make_mesh((dp, model_parallel), ("data", "model"),
                      jax.devices()[: dp * model_parallel])
