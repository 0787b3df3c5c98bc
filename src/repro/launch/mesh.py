"""Production mesh builders.

Single pod: (data=16, model=16) — 256 chips (one v5e pod).
Multi-pod:  (pod=2, data=16, model=16) — 512 chips; the 'pod' axis is pure
data-parallel and maps to DCN (gradients crossing it can be int8-compressed,
see optim.compress). Functions, not module constants: importing this module
never touches jax device state.
"""
from __future__ import annotations

from typing import Optional

import jax


def _auto(n_axes: int):
    """GSPMD-propagated (Auto) axes: the sharding rules annotate, XLA plans."""
    return (jax.sharding.AxisType.Auto,) * n_axes


def make_production_mesh(*, multi_pod: bool = False,
                         model: int = 16) -> jax.sharding.Mesh:
    """256 chips/pod; ``model`` sets the TP degree (data = 256/model).
    Non-default TP is a §Perf hillclimb lever (tp4/tp8 variants)."""
    data = 256 // model
    shape = (2, data, model) if multi_pod else (data, model)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_host_mesh(model: int = 1,
                   n_devices: Optional[int] = None) -> jax.sharding.Mesh:
    """Small mesh over the first ``n_devices`` devices (default: all).

    Raises ValueError (not a bare assert, which ``python -O`` strips into a
    garbage-shaped mesh) when ``model`` exceeds or doesn't divide the
    device count.
    """
    devices = jax.devices()[:n_devices]
    n = len(devices)
    if model < 1:
        raise ValueError(f"model={model} must be >= 1")
    if model > n:
        raise ValueError(
            f"model={model} exceeds the {n} available device(s); force more "
            "with XLA_FLAGS=--xla_force_host_platform_device_count=N or "
            "lower the model-parallel degree")
    if n % model != 0:
        raise ValueError(
            f"device count {n} is not divisible by model={model}")
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=_auto(2), devices=devices)
