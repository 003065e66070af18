"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module constant) so that
importing this module never touches jax device state — the dry-run must
set XLA_FLAGS before the first jax initialization.

Topology mapping (TPU v5e pods): ``model`` is the innermost axis (ICI-
adjacent chips — TP/EP collectives ride the fastest links), ``data``
spans the pod (FSDP/DP all-reduces), and ``pod`` crosses the DCN (only
pure-DP gradient reductions — optionally int8-compressed — cross pods).
"""

from __future__ import annotations

import jax

from jax.sharding import AxisType


def _axis_kwargs(n_axes: int) -> dict:
    return {"axis_types": (AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devices)} — "
            "set XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "before the first jax import (see launch/dryrun.py)"
        )
    return jax.make_mesh(shape, axes, devices=devices, **_axis_kwargs(len(axes)))


def make_test_mesh(shape=(2, 4), axes=("data", "model")):
    """Small mesh for CPU tests (requires forced host device count)."""
    n = 1
    for s in shape:
        n *= s
    return jax.make_mesh(
        shape, axes, devices=jax.devices()[:n], **_axis_kwargs(len(axes))
    )
