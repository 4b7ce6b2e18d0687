"""Production mesh factory.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  The dry-run launcher sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import;
everything else in the repo sees the real single device.

Mesh geometry (TPU v5e posture):
  single pod:  (data, model) = (16, 16)        — 256 chips
  multi pod:   (pod, data, model) = (2, 16, 16) — 512 chips
``model`` is the intra-pod TP/EP axis (ICI-local); ``data`` carries
DP/FSDP; ``pod`` carries cross-pod DP (optionally pipeline stages via
dist.pipeline_parallel).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_debug_mesh(n_data: int = 2, n_model: int = 2):
    """Small mesh for CPU multi-device tests (device count set by caller)."""
    return jax.make_mesh((n_data, n_model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
