"""Production meshes.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the batch shards over
(pod, data) so the only traffic crossing the slow inter-pod links is the
once-per-step gradient reduction (+ MoE router stats), which is the standard
DCN-friendly arrangement.

Defined as functions, not module constants: importing this module never
touches jax device state (device count is locked at first jax init — the
dry-run driver must set XLA_FLAGS before any jax import).
"""
from __future__ import annotations

from typing import Optional

import jax

__all__ = ["make_mesh_auto", "make_production_mesh", "make_serve_mesh",
           "make_test_mesh"]


def make_mesh_auto(shape, axes):
    """``jax.make_mesh`` with every axis of type Auto (sharding propagated
    by the compiler)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_auto(shape, axes)


def make_test_mesh(data: int = 2, model: int = 4):
    """Small mesh for CI-grade sharding tests (8 host-platform devices)."""
    return make_mesh_auto((data, model), ("data", "model"))


def make_serve_mesh(model: int = 1, data: Optional[int] = None):
    """The serving tier's (data x model) mesh over the visible devices.

    ``model`` is the Co-shard width (1 = pure data parallelism — every
    ``ConvServer`` works on any dense model); ``data`` defaults to
    ``device_count // model`` so the mesh always covers the whole slice.
    The batch shards over ``data`` and every conv's ``Co/Cob`` blocks over
    ``model`` (DESIGN.md §15).
    """
    n = jax.device_count()
    if n % model:
        raise ValueError(f"model={model} must divide device count {n}")
    if data is None:
        data = n // model
    return make_mesh_auto((data, model), ("data", "model"))
