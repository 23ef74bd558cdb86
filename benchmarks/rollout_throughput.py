"""Rollout throughput: event-driven oracle vs array-native batched engine.

Measures simulated *request-rounds per second* (requests simulated x
scheduling rounds / wall time) for the same scenario on both engines. The
event-driven ``MultiEdgeSim`` pays Python heap events and per-round numpy
scheduling for one instance at a time; the batched engine jits one
``step_round`` and vmaps it over an instance axis, so throughput scales
with batch. The acceptance bar this reports against: >= 10x at batch >= 64
on the default scenario.

``--fleet 1,2,4,8`` additionally runs the fleet-sharded rollout
(:mod:`repro.serving.fleet`) at each shard count on a ``("fleet",)`` device
mesh and reports the scaling curve (request-rounds/s per shard count,
speedup vs 1 shard, Zipf placement imbalance and cross-shard transfer
accounting). Shard counts beyond 1 need real or forced host devices —
launch through benchmarks/run_hw.sh with HOST_DEVICES set.

Run:  PYTHONPATH=src python benchmarks/rollout_throughput.py
      PYTHONPATH=src python benchmarks/rollout_throughput.py \\
          --rounds 4 --batch 8            # CI smoke
      PYTHONPATH=src python benchmarks/rollout_throughput.py \\
          --batch 1,8,64,256 --backend greedy
      HOST_DEVICES=8 benchmarks/run_hw.sh rollout_throughput \\
          --fleet 1,2,4,8 --fleet-batch 64
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

from repro.platform import setup_compile_cache
from repro.serving import (ASSIGN_FNS, CentralController, EngineConfig,
                           MultiEdgeSim, SimConfig, init_batch, make_rollout,
                           resolve_assign_fn, summarize)
from repro.workloads import materialize_round_batch, scenario

REPORT_SCHEMA = "corais.rollout_throughput.v1"

#: heuristic backends only: this benchmark pairs each engine backend with
#: the event-driven controller by name, and the policy factory needs
#: trained params (see benchmarks/policy_latency.py for policy timing)
BACKENDS = sorted(k for k, v in ASSIGN_FNS.items()
                  if not getattr(v, "_assign_factory", False))


def bench_event_sim(name: str, backend: str, num_edges: int, rounds: int,
                    interval: float, seed: int, repeat: int) -> dict:
    """One event-driven run per repeat; returns the best wall time."""
    walls, submitted, completed = [], 0, 0
    for r in range(repeat):
        cc = CentralController(scheduler=backend)
        sim = MultiEdgeSim(
            SimConfig(num_edges=num_edges, round_interval=interval,
                      seed=seed, exec_noise=0.0), cc)
        t0 = time.perf_counter()
        m = sim.drive(scenario(name), until=rounds * interval,
                      run_until=1e5, seed=seed)
        walls.append(time.perf_counter() - t0)
        submitted, completed = m["submitted"], m["completed"]
    wall = min(walls)
    request_rounds = submitted * rounds
    return {
        "wall_s": wall,
        "requests": submitted,
        "completed": completed,
        "request_rounds": request_rounds,
        "request_rounds_per_s": request_rounds / max(wall, 1e-12),
    }


def bench_engine(name: str, backend: str, num_edges: int, rounds: int,
                 interval: float, seed: int, batch: int, repeat: int) -> dict:
    arrivals = materialize_round_batch(
        scenario(name), num_edges, rounds, interval, batch, base_seed=seed)
    cfg = EngineConfig(num_edges=num_edges, num_rounds=rounds,
                       round_interval=interval,
                       max_per_round=arrivals["mask"].shape[-1])
    state0 = init_batch(cfg, range(seed, seed + batch))
    keys = jax.random.split(jax.random.PRNGKey(seed), batch)
    run = make_rollout(cfg, resolve_assign_fn(backend), batch=True)

    t0 = time.perf_counter()
    jax.block_until_ready(run(state0, arrivals, keys))
    compile_s = time.perf_counter() - t0
    walls = []
    final = None
    for _ in range(repeat):
        t0 = time.perf_counter()
        final, _infos = run(state0, arrivals, keys)
        jax.block_until_ready(final)
        walls.append(time.perf_counter() - t0)
    wall = min(walls)
    m = summarize(final)
    request_rounds = m["submitted"] * rounds
    return {
        "batch": batch,
        "wall_s": wall,
        "compile_s": compile_s,
        "requests": m["submitted"],
        "completed": m["completed"],
        "request_rounds": request_rounds,
        "request_rounds_per_s": request_rounds / max(wall, 1e-12),
    }


def bench_fleet(name: str, backend: str, num_edges: int, rounds: int,
                interval: float, seed: int, batch: int, shards: int,
                skew: float, repeat: int) -> dict:
    """One fleet-sharded rollout at ``shards`` shards: Zipf-partitioned
    placement, shard_map rollout, psum-reduced summary partials."""
    from repro.launch.mesh import make_fleet_mesh
    from repro.serving import (apply_partition, fleet_summary,
                               make_fleet_rollout, zipf_partition)

    mesh = make_fleet_mesh(shards)
    arrivals = materialize_round_batch(
        scenario(name), num_edges, rounds, interval, batch, base_seed=seed)
    cfg = EngineConfig(num_edges=num_edges, num_rounds=rounds,
                       round_interval=interval,
                       max_per_round=arrivals["mask"].shape[-1])
    part = zipf_partition(batch, shards, skew=skew, seed=seed)
    states = apply_partition(part, init_batch(cfg, range(seed, seed + batch)))
    arrivals = apply_partition(part, arrivals)
    keys = apply_partition(
        part, np.asarray(jax.random.split(jax.random.PRNGKey(seed), batch)))
    displaced = part.placed_displaced
    run = make_fleet_rollout(cfg, resolve_assign_fn(backend), mesh)

    t0 = time.perf_counter()
    jax.block_until_ready(run(states, arrivals, keys, displaced))
    compile_s = time.perf_counter() - t0
    walls, partials = [], None
    for _ in range(repeat):
        t0 = time.perf_counter()
        partials = run(states, arrivals, keys, displaced)
        jax.block_until_ready(partials)
        walls.append(time.perf_counter() - t0)
    wall = min(walls)
    m = fleet_summary(partials)
    request_rounds = m["submitted"] * rounds
    return {
        "shards": shards,
        "batch": batch,
        "wall_s": wall,
        "compile_s": compile_s,
        "requests": m["submitted"],
        "completed": m["completed"],
        "request_rounds": request_rounds,
        "request_rounds_per_s": request_rounds / max(wall, 1e-12),
        "cross_shard_transferred": m["cross_shard_transferred"],
        "intra_fleet_transferred": m["intra_fleet_transferred"],
        "cross_shard_frac": m["cross_shard_frac"],
        "imbalance": part.imbalance_report(),
    }


def main() -> None:
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenario", default="uniform_iid")
    ap.add_argument("--backend", default="greedy", choices=BACKENDS)
    ap.add_argument("--edges", type=int, default=5)
    ap.add_argument("--rounds", type=int, default=12)
    ap.add_argument("--interval", type=float, default=0.25)
    ap.add_argument("--batch", default="1,8,64",
                    help="comma list of engine batch sizes")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--repeat", type=int, default=3)
    ap.add_argument("--fleet", default=None,
                    help="comma list of fleet shard counts (e.g. 1,2,4,8); "
                         "runs the sharded rollout scaling curve. Counts > 1 "
                         "need host devices: HOST_DEVICES=8 "
                         "benchmarks/run_hw.sh rollout_throughput ...")
    ap.add_argument("--fleet-batch", type=int, default=64,
                    help="instance batch for the fleet scaling curve")
    ap.add_argument("--fleet-skew", type=float, default=0.9,
                    help="Zipf skew of the fleet home-shard draw")
    ap.add_argument("--out", default=None,
                    help="report path (default results/rollout_throughput.json)")
    args = ap.parse_args()
    batches = [int(b) for b in str(args.batch).split(",")]
    fleet_shards = ([int(s) for s in str(args.fleet).split(",")]
                    if args.fleet else [])
    if fleet_shards and max(fleet_shards) > len(jax.devices()):
        raise SystemExit(
            f"--fleet {args.fleet} needs {max(fleet_shards)} device(s) but "
            f"only {len(jax.devices())} visible; launch through "
            f"HOST_DEVICES={max(fleet_shards)} benchmarks/run_hw.sh")

    print(f"== rollout throughput: scenario={args.scenario} "
          f"backend={args.backend} rounds={args.rounds} ==")
    event = bench_event_sim(args.scenario, args.backend, args.edges,
                            args.rounds, args.interval, args.seed, args.repeat)
    print(f"  event-driven       {event['request_rounds_per_s']:12.0f} "
          f"req-rounds/s  ({event['requests']} requests, "
          f"{event['wall_s'] * 1e3:.1f} ms)")

    engine_rows = []
    for batch in batches:
        row = bench_engine(args.scenario, args.backend, args.edges,
                           args.rounds, args.interval, args.seed, batch,
                           args.repeat)
        row["speedup_vs_event"] = (row["request_rounds_per_s"]
                                   / max(event["request_rounds_per_s"], 1e-12))
        engine_rows.append(row)
        print(f"  engine (batch={batch:4d}) {row['request_rounds_per_s']:12.0f} "
              f"req-rounds/s  ({row['requests']} requests, "
              f"{row['wall_s'] * 1e3:.1f} ms, {row['speedup_vs_event']:.1f}x)")

    fleet_rows = []
    for shards in fleet_shards:
        row = bench_fleet(args.scenario, args.backend, args.edges,
                          args.rounds, args.interval, args.seed,
                          args.fleet_batch, shards, args.fleet_skew,
                          args.repeat)
        row["speedup_vs_1shard"] = (
            row["request_rounds_per_s"]
            / max(fleet_rows[0]["request_rounds_per_s"], 1e-12)
            if fleet_rows else 1.0)
        fleet_rows.append(row)
        imb = row["imbalance"]
        print(f"  fleet ({shards:2d} shard{'s' if shards > 1 else ' '}, "
              f"batch={row['batch']}) {row['request_rounds_per_s']:12.0f} "
              f"req-rounds/s  ({row['wall_s'] * 1e3:.1f} ms, "
              f"{row['speedup_vs_1shard']:.2f}x vs 1 shard, "
              f"home imbalance {imb['home_imbalance']:.2f}, "
              f"{imb['displaced_instances']} displaced, "
              f"cross-shard {row['cross_shard_transferred']})")

    report = {
        "schema": REPORT_SCHEMA,
        "config": {
            "scenario": args.scenario, "backend": args.backend,
            "num_edges": args.edges, "rounds": args.rounds,
            "interval": args.interval, "seed": args.seed,
            "repeat": args.repeat, "batches": batches,
            "fleet_shards": fleet_shards, "fleet_batch": args.fleet_batch,
            "fleet_skew": args.fleet_skew,
        },
        "event_sim": event,
        "engine": engine_rows,
        "fleet": fleet_rows,
    }
    out = args.out or os.path.join(os.path.dirname(__file__), "..",
                                   "results", "rollout_throughput.json")
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(out, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
    print(f"== report written to {os.path.abspath(out)} ==")


if __name__ == "__main__":
    main()
