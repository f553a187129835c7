"""Production mesh construction.

Functions only — importing this module never touches jax device state, so
dryrun.py can set XLA_FLAGS before anything initializes the backend.

Mesh geometry (TPU v5e pods of 256 chips):
  single-pod:  (data=16, model=16)
  multi-pod:   (pod=2, data=16, model=16) — 512 chips.

Axis roles: batch shards over ('pod', 'data'); tensor-parallel over
('model',); FSDP parameter sharding over ('data',); optimizer states
(ZeRO-1) additionally over ('data',).  The distributed cache uses a flat
view of all devices ('cache',).
"""

from __future__ import annotations

import jax

__all__ = ["make_mesh_compat", "shard_map_compat", "make_production_mesh",
           "make_cache_mesh", "batch_axes", "AXIS_DATA", "AXIS_MODEL",
           "AXIS_POD"]

AXIS_POD = "pod"
AXIS_DATA = "data"
AXIS_MODEL = "model"


def make_mesh_compat(shape, axes):
    """``jax.make_mesh`` with every axis of type Auto."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def shard_map_compat(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the replication check off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = (AXIS_POD, AXIS_DATA, AXIS_MODEL) if multi_pod else (AXIS_DATA, AXIS_MODEL)
    return make_mesh_compat(shape, axes)


def make_cache_mesh(n_devices: int | None = None):
    """1-D mesh over all (or n) devices for the sharded key-value cache.

    On a TPU host these are the real chips.  For CPU-only multi-device runs
    (the sharded tests / benches), set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` BEFORE the first
    jax import — the fake-device count is locked at backend init, which is
    why those runs live in subprocesses (see tests/test_sharded_engine.py
    and benchmarks/sharded_bench.py).
    """
    n = n_devices or len(jax.devices())
    return make_mesh_compat((n,), ("cache",))


def batch_axes(mesh) -> tuple:
    """Mesh axes the global batch shards over."""
    return tuple(a for a in (AXIS_POD, AXIS_DATA) if a in mesh.shape)


def make_debug_mesh(shape=(1, 1), axes=(AXIS_DATA, AXIS_MODEL)):
    """Tiny mesh for CPU tests (shape product must be <= live devices)."""
    return make_mesh_compat(shape, axes)
