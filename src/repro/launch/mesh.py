"""Mesh construction for both training stacks.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.  Two consumers:

- the LM production stack: the single-pod mesh is 16 x 16 = 256 chips
  (TPU v5e pod); multi-pod adds a leading ``pod`` axis (2 pods = 512 chips).
  Axis roles (DESIGN.md §6): ``pod`` — data parallelism across the DCN,
  ``data`` — FSDP within a pod, ``model`` — tensor parallelism within a pod.
- the GFN trainer's :class:`repro.algo.plan.DataParallelPlan`, which builds
  a 1-D ``("batch",)`` mesh here — over a *subset* of the visible devices
  when ``--devices N`` asks for fewer than are attached (virtual CPU
  devices included).
"""
from __future__ import annotations

import math

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Generic mesh for plans/tests/benchmarks (e.g. ``((4,), ("batch",))``
    on an 8-virtual-device CPU).  Uses ``jax.make_mesh`` when the shape
    consumes every visible device (it reorders devices for locality) and
    falls back to the first ``prod(shape)`` devices otherwise.  Either way
    the axes are ``Auto``: the plans place data with ``shard_map`` and
    ``NamedSharding``, not with sharding-in-types."""
    shape = tuple(shape)
    n = math.prod(shape)
    if n == jax.device_count():
        return jax.make_mesh(shape, tuple(axes), axis_types=(
            jax.sharding.AxisType.Auto,) * len(shape))
    if n > jax.device_count():
        raise ValueError(
            f"mesh shape {shape} needs {n} devices but only "
            f"{jax.device_count()} are visible; on CPU, set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n}")
    devices = np.asarray(jax.devices()[:n]).reshape(shape)
    return jax.sharding.Mesh(devices, tuple(axes))
