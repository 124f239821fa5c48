"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module must
never touch jax device state (the dry-run pins the device count via
XLA_FLAGS before any jax initialization).

Mesh semantics:
  single-pod: (data=16, model=16)            — 256 chips (one v5e pod)
  multi-pod:  (pod=2, data=16, model=16)     — 512 chips (2 pods)

`model` is the TP/EP axis (intra-pod, fastest ICI); `data` is in-pod
data parallel + FSDP; `pod` is cross-pod data parallel (params
replicated per pod; one cross-pod gradient all-reduce per step).

Every axis is `Auto`: the model code places values with
`with_sharding_constraint`, which refuses `Explicit` axes (the default
of `jax.make_mesh`).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — tests/examples."""
    n = len(jax.devices())
    data = min(data, n // max(model, 1))
    return jax.make_mesh((max(data, 1), max(model, 1)), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
