"""Production mesh construction (task spec MULTI-POD DRY-RUN step 1).

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state (device count is locked at first jax init).
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import AxisType, Mesh


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the program places arrays with
    ``with_sharding_constraint`` and shard_map, which Explicit axes (the
    default of ``make_mesh``) refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi-pod = 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1,
                   axis_names: tuple[str, str] = ("data", "model")):
    """Mesh over the actually-available devices (tests, examples).

    Raises ``ValueError`` (not ``assert``, which vanishes under ``python
    -O``) when the device count does not divide: the fleet mesh and every
    sharded test build on this helper, so a bad layout must fail loudly."""
    n = len(jax.devices())
    if model_parallel < 1 or n % model_parallel != 0:
        raise ValueError(
            f"cannot build a host mesh: {n} available device(s) not "
            f"divisible by model_parallel={model_parallel}")
    return auto_mesh((n // model_parallel, model_parallel), axis_names)


def make_fleet_mesh(num_shards: int | None = None, *, dry_run: bool = False):
    """1-D ``("fleet",)`` mesh for fleet-sharded rollouts
    (:mod:`repro.serving.fleet`).

    Locally this builds on :func:`make_host_mesh`: every available device
    lands on the fleet axis (``num_shards=None``), or the first
    ``num_shards`` devices do — the subset form exists for scaling curves
    (1, 2, 4, 8 shards on one forced 8-device host). With ``dry_run=True``
    the 256-chip :func:`make_production_mesh` pod is flattened onto one
    fleet axis (usable only under the dry-run harness that forces that many
    devices)."""
    if dry_run:
        prod = make_production_mesh()
        return Mesh(prod.devices.reshape(-1), ("fleet",))
    devices = jax.devices()
    n = len(devices)
    if num_shards is None or num_shards == n:
        host = make_host_mesh(1, axis_names=("fleet", "model"))
        return Mesh(host.devices.reshape(-1), ("fleet",))
    if not 1 <= num_shards <= n:
        raise ValueError(
            f"cannot build a fleet mesh with {num_shards} shard(s): "
            f"{n} device(s) available")
    return Mesh(np.asarray(devices[:num_shards]), ("fleet",))
