"""Production mesh construction.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the 'pod' axis is
the slow (DCN / inter-pod) dimension -- parallel.hierarchical spends
its T_pod budget exactly there.

A FUNCTION, not a module constant: importing this module must never
touch jax device state (smoke tests see 1 CPU device; only
launch/dryrun.py forces 512 host devices via XLA_FLAGS before any jax
import).
"""
from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    """Mesh whose axes are all `Auto`: the model substrate places arrays
    with `NamedSharding` and `with_sharding_constraint` and lets the
    compiler propagate the rest. `jax.make_mesh` defaults to `Explicit`
    axes, under which every gather and reshape of a sharded array must
    name its output sharding."""
    from jax.sharding import AxisType

    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many devices the host actually has
    (tests / examples)."""
    return _auto_mesh((data, model), ("data", "model"))


def make_batch_mesh(devices=None):
    """1D data-parallel mesh over an explicit device list — the lock
    substrate's exploration axis (`Session.grid/sweep/run_batch` shard
    the flattened (lattice points x seeds) batch over it).

    `devices` is a sequence of jax devices (default: all local devices).
    Distinct from `make_host_mesh`: exploration batches shard over ONE
    axis of explicitly chosen devices, so the same helper serves both a
    real multi-chip host and an `--xla_force_host_platform_device_count`
    forced-CPU test topology.
    """
    import numpy as np
    from jax.sharding import Mesh

    devices = list(jax.local_devices() if devices is None else devices)
    if not devices:
        raise ValueError("make_batch_mesh needs at least one device")
    return Mesh(np.array(devices), ("batch",))
