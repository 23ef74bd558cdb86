"""Fleet-sharded rollouts: the batched engine over a real device mesh.

The array-native engine (:mod:`repro.serving.engine`) vmaps a (B,) batch of
independent cluster instances on one device. This module spreads that batch
over a 1-D ``("fleet",)`` device mesh (:func:`repro.launch.mesh
.make_fleet_mesh`) with ``shard_map``: each device rolls its slice of
instances forward with the exact same jitted ``make_rollout(batch=True)``
program, then the per-shard summary partials (:func:`repro.serving.engine
.summarize_partials` — counts, a fixed-bin response-time histogram for
p50/p95, per-edge completions) are reduced across the fleet with
psum/pmax. The host only ever sees the few-hundred-float reduced summary,
never a device_get of B full slot tables — which is what lets one run
simulate thousands of clusters.

Placement is where fleets stop being embarrassingly parallel.
:func:`zipf_partition` models the real-world skew ROADMAP item 1 calls
for: every instance gets a *home* shard drawn from a Zipf popularity law
over shards (hot regions attract more clusters), while the actual
*placement* is capacity-balanced (``shard_map`` needs exactly B/S
instances per device). Instances that could not fit their home shard are
*displaced* — their traffic had to leave its region — and the summary
accounts transfers of displaced instances as cross-shard traffic,
separate from intra-fleet transfers. :meth:`FleetPartition
.imbalance_report` quantifies the skew the balancer absorbed.

Equivalence: a fleet-sharded rollout reduces to exactly the single-device
vmap engine's summary (instances never interact across shards; the only
cross-device ops are the final psums) — pinned at 1e-5 on a forced
8-device host mesh by tests/fleet_child.py.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.serving import engine
from repro.sharding.specs import arrival_specs, engine_state_specs
from repro.workloads.base import edge_weights


@dataclasses.dataclass(frozen=True)
class FleetPartition:
    """Instance-to-shard assignment for one fleet rollout.

    ``home`` is the Zipf-drawn region of each instance; ``shard`` the
    capacity-balanced placement actually used on the mesh; ``order`` the
    permutation that groups placements into the contiguous (B/S)-blocks
    ``shard_map`` splits the leading axis into (apply it with
    :func:`apply_partition` before running)."""

    num_shards: int
    home: np.ndarray   # (B,) int — Zipf-popular home shard per instance
    shard: np.ndarray  # (B,) int — balanced placement shard per instance
    order: np.ndarray  # (B,) int — permutation grouping placement shards

    @property
    def displaced(self) -> np.ndarray:
        """(B,) bool, instance order: placed off its home shard."""
        return self.home != self.shard

    @property
    def placed_displaced(self) -> np.ndarray:
        """(B,) bool in *placement* order — pass this to the fleet rollout
        so cross-shard accounting travels with the reordered instances."""
        return self.displaced[self.order]

    def imbalance_report(self, loads=None) -> dict:
        """How skewed the requested (home) load was vs what each shard
        actually runs. ``loads`` weights instances (e.g. real arrival
        counts from an arrival batch's ``mask.sum``); defaults to 1 per
        instance. ``home_imbalance`` is max/mean of per-shard home load —
        1.0 is perfectly uniform."""
        b = len(self.home)
        loads = np.ones(b) if loads is None else np.asarray(loads, float)
        home_load = np.bincount(self.home, weights=loads,
                                minlength=self.num_shards)
        placed_load = np.bincount(self.shard, weights=loads,
                                  minlength=self.num_shards)
        mean = max(loads.sum() / self.num_shards, 1e-12)
        displaced = int(self.displaced.sum())
        return {
            "num_shards": self.num_shards,
            "capacity": b // self.num_shards,
            "home_load": [float(x) for x in home_load],
            "placed_load": [float(x) for x in placed_load],
            "home_imbalance": float(home_load.max() / mean),
            "placed_imbalance": float(placed_load.max() / mean),
            "displaced_instances": displaced,
            "displaced_frac": displaced / max(b, 1),
        }


def zipf_partition(num_instances: int, num_shards: int, *, skew: float = 0.0,
                   seed: int = 0) -> FleetPartition:
    """Draw each instance's home shard from a Zipf popularity law
    (rank-k shard has weight (k+1)^-skew; ``skew=0`` is uniform) and place
    instances with a capacity-balanced first-fit: home shard while it has
    room, else the least-loaded shard with remaining capacity. The gap
    between the two is exactly the load the fleet must move cross-shard."""
    if num_instances % num_shards != 0:
        raise ValueError(
            f"cannot partition {num_instances} instance(s) over "
            f"{num_shards} shard(s): shard_map needs equal blocks "
            f"(instances % shards == 0)")
    probs = edge_weights(num_shards, skew)
    rng = np.random.default_rng(seed)
    home = rng.choice(num_shards, size=num_instances, p=probs)
    cap = num_instances // num_shards
    counts = np.zeros(num_shards, np.int64)
    shard = np.empty(num_instances, np.int64)
    for i, h in enumerate(home):
        if counts[h] < cap:
            shard[i] = h
        else:
            shard[i] = int(np.argmin(np.where(counts < cap, counts,
                                              num_instances + 1)))
        counts[shard[i]] += 1
    order = np.argsort(shard, kind="stable")
    return FleetPartition(num_shards=num_shards, home=home, shard=shard,
                          order=order)


def apply_partition(part: FleetPartition, tree):
    """Reorder a batched pytree's leading instance axis into the
    partition's placement order (contiguous per-shard blocks)."""
    return jax.tree.map(lambda x: np.asarray(x)[part.order], tree)


def make_fleet_rollout(cfg: engine.EngineConfig, assign_fn, mesh, *,
                       axis: str = "fleet",
                       hist_bins: int = engine.HIST_BINS,
                       hist_max: float = engine.HIST_MAX,
                       slo: Optional[float] = None,
                       drain_to: Optional[float] = engine.DRAIN_HORIZON):
    """Build ``run(states, arrivals, keys, displaced=None) -> partials``:
    the fleet-sharded twin of ``make_rollout(batch=True)`` + ``summarize``.

    Inputs are the same (B,)-leading batched pytrees the vmap engine takes
    (``init_batch`` states, ``materialize_round_batch`` arrivals, (B,)
    split keys), reordered with :func:`apply_partition` when using a
    skewed partition; B must divide by the mesh's fleet-axis size. The
    return value is the psum/pmax-reduced :func:`repro.serving.engine
    .summarize_partials` dict (replicated, small) — feed it to
    :func:`repro.serving.engine.partials_to_summary` for the metrics
    dict. ``displaced`` is ``FleetPartition.placed_displaced`` and drives
    the cross-shard transfer split."""
    num_shards = int(mesh.shape[axis])
    inner = engine.make_rollout(cfg, assign_fn, batch=True, drain_to=drain_to)

    def body(states, arrivals, keys, displaced):
        final, _infos = inner(states, arrivals, keys)
        p = engine.summarize_partials(final, hist_bins=hist_bins,
                                      hist_max=hist_max, displaced=displaced,
                                      slo=slo)
        return {k: (jax.lax.pmax(v, axis) if k in engine.PARTIAL_MAX_KEYS
                    else jax.lax.psum(v, axis))
                for k, v in p.items()}

    cache: dict = {}

    def run(states, arrivals, keys, displaced=None):
        b = int(np.shape(arrivals["size"])[0])
        if b % num_shards != 0:
            raise ValueError(
                f"batch of {b} instance(s) does not divide over the "
                f"{num_shards}-shard fleet axis {axis!r}; pad the batch or "
                f"shrink the mesh")
        if displaced is None:
            displaced = np.zeros(b, bool)
        sig = (jax.tree.structure(states), jax.tree.structure(arrivals))
        fn = cache.get(sig)
        if fn is None:
            in_specs = (engine_state_specs(states, axis),
                        arrival_specs(arrivals, axis),
                        arrival_specs(keys, axis), P(axis))
            fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                       out_specs=P(), check_vma=False))
            cache[sig] = fn
        return fn(states, arrivals, keys, displaced)

    return run


def fleet_summary(partials: dict, *, slo: Optional[float] = None,
                  hist_max: float = engine.HIST_MAX) -> dict:
    """Reduced fleet partials -> ``summarize``-style metrics dict
    (thin alias of :func:`repro.serving.engine.partials_to_summary`)."""
    return engine.partials_to_summary(partials, slo=slo, hist_max=hist_max)
