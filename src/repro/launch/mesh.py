"""Production mesh builders.

Single pod: 16x16 = 256 chips ("data" x "model"). Multi-pod: 2x16x16 = 512
chips ("pod" x "data" x "model") — the pod axis is pure data parallelism
(cross-pod all-reduce rides DCN/ICI), data is FSDP, model is tensor
parallelism.

Functions, not module constants: importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before any jax init).

Every mesh has ``Auto`` axes: the sharding rules place arrays with
``with_sharding_constraint`` and leave the rest to GSPMD, which
``jax.make_mesh``'s default ``Explicit`` axes refuse.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple, axes: tuple, devices=None):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for the production mesh, found {len(devices)}; "
            "the dry-run must set XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "before any jax import")
    return _auto_mesh(shape, axes, devices[:n])


def make_test_mesh(n_devices: int | None = None):
    """Small mesh over whatever devices exist (CPU tests)."""
    n = n_devices or len(jax.devices())
    model = 1
    for m in (4, 2, 1):
        if n % m == 0:
            model = m
            break
    return _auto_mesh((n // model, model), ("data", "model"),
                      jax.devices()[:n])


def make_serve_mesh(model_par: int = 1, n_devices: int | None = None):
    """Serving mesh ("data", "model") — the paper's chips × banks.

    ``model_par`` devices per model replica (tensor/bank parallelism: the
    "model" axis splits every projection's output columns and the
    PackedWeight planes); the remaining ``n // model_par`` devices shard the
    continuous-batching slot grid (the "data" axis — the paper's chips).
    ``ServeEngine(..., mesh=make_serve_mesh(...))`` does the rest
    (DESIGN.md §5).

    CPU-only boxes: force a multi-device host *before any jax import* —

        XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
            python -m repro.launch.serve --arch qwen3-0.6b --reduced \\
            --model-par 2
    """
    devices = jax.devices()
    n = n_devices or len(devices)
    if n > len(devices):
        raise RuntimeError(
            f"need {n} devices, found {len(devices)}; on CPU set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N before any "
            "jax import")
    if model_par < 1 or n % model_par:
        raise ValueError(f"model_par={model_par} must divide n_devices={n}")
    return _auto_mesh((n // model_par, model_par), ("data", "model"),
                      devices[:n])
