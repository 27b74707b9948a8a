"""Production mesh construction.

Axes:
  * pod   — 2 pods of 256 chips; pure data parallelism over slow DCN links
            (params replicated per pod, gradients synced across — optionally
            int8-compressed, see train/compression.py).
  * data  — 16-way FSDP + batch data parallelism within a pod.
  * model — 16-way tensor / expert / sequence parallelism (fast ICI ring).

Functions, not module constants: importing this module never touches jax
device state (the dry-run must set XLA_FLAGS before first jax init).

Every axis is ``AxisType.Auto``: the model places activations with
``with_sharding_constraint`` and leaves the collectives to the compiler,
which explicit axes (``jax.make_mesh``'s default) do not allow.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

from repro.models.sharding import ShardCtx


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) >= need:           # 512 placeholders: single-pod slice
        return _auto_mesh(shape, axes, devs[:need])
    raise RuntimeError(
        f"need {need} devices for mesh {shape}, have {len(devs)} — run "
        "under XLA_FLAGS=--xla_force_host_platform_device_count=512")


def make_test_mesh(data: int = 1, model: int = 1, pod: int = 0):
    """Small mesh for CPU tests (uses however many devices exist)."""
    shape = (pod, data, model) if pod else (data, model)
    axes = ("pod", "data", "model") if pod else ("data", "model")
    need = int(np.prod(shape))
    return _auto_mesh(shape, axes, jax.devices()[:need])


def _auto_mesh(shape, axes, devices):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def shard_ctx(mesh: Mesh) -> ShardCtx:
    pod = "pod" if "pod" in mesh.axis_names else None
    return ShardCtx(mesh=mesh, fsdp="data", tp="model", pod=pod)
