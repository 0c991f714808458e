"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before the first jax init).

Single pod: (data=16, model=16) = 256 chips (one v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the `pod` axis is pure
data parallelism — the only traffic that crosses the inter-pod DCN/ICI
boundary is the once-per-step gradient all-reduce.
"""
from __future__ import annotations

import jax


def make_mesh_compat(shape, axes):
    """`jax.make_mesh` with every axis in Auto sharding mode (the default
    mode of the installed JAX is Explicit, which this code does not use)."""
    types = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=types)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_compat(shape, axes)


def make_host_mesh():
    """Whatever this host actually has (smoke tests / examples): 1xN."""
    n = len(jax.devices())
    return jax.make_mesh((1, n), ("data", "model"))
