"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module must never
touch jax device state (smoke tests see 1 CPU device; only dryrun.py sets
XLA_FLAGS for 512 placeholder devices before importing jax).

Mesh semantics (DESIGN.md section 5): `data` = the paper's trainer axis,
`model` = the paper's sparse-parameter-server axis, `pod` = pod-level data
parallelism (and the EASGD replica axis).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes) -> jax.sharding.Mesh:
    """jax.make_mesh with Auto axes: the step builders rely on the SPMD
    partitioner to propagate shardings (jax 0.9 defaults to Explicit)."""
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(shape=(2, 4), axes=("data", "model")) -> jax.sharding.Mesh:
    """Small mesh for CPU integration tests (requires
    xla_force_host_platform_device_count >= prod(shape))."""
    return _auto_mesh(shape, axes)


def make_host_mesh(n_hosts: int, axis: str = "data") -> jax.sharding.Mesh:
    """1-D mesh over the data-parallel hosts of the multi-host cached tier
    (core/cache.py): the capacity tier row-shards over this axis and the
    routed sparse update shard_maps over it (train/steps.py
    build_cached_train_step's multi-host dispatch)."""
    return _auto_mesh((n_hosts,), (axis,))
