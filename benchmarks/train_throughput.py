"""Temporal-RL training throughput: host loop vs scanned epoch vs sharded.

Measures *updates (batches) per second* and *episode-rounds per second*
(batches/s x batch_size x num_rounds) for the three execution paths of the
temporal REINFORCE trainer on the same scenario:

* ``host-loop`` — the pre-epoch trainer semantics: one jitted update per
  batch, episodes materialized by the host numpy sampler each batch,
  faults attached on host, and a blocking ``float(loss)`` sync after every
  update (the dispatch bubble the scanned path removes).
* ``scan-epoch`` — :func:`repro.core.train.make_temporal_epoch_step`: K
  updates per dispatch under one ``lax.scan``, episodes and faults drawn
  in-jit by the device sampler, metrics stacked on device and drained once
  per epoch.
* ``sharded`` — the same epoch step shard_map'd over the ``("fleet",)``
  device mesh (batch axis data-parallel, pmean-averaged grads). Skipped
  with a note when only one device is visible — launch through
  ``HOST_DEVICES=8 benchmarks/run_hw.sh train_throughput`` to force a
  host mesh (single-core containers then record *parity*, not speedup:
  8 virtual devices share one core).

Timing is steady-state: every mode runs one untimed warmup dispatch
(compilation + first materialization), then the measured window, closed
with a single ``block_until_ready``. The host-side episode sampling is
*inside* the measured window for every mode — that asymmetry (numpy
sampler on host vs jax sampler in-jit) is precisely what the benchmark
exists to show, and is why the chaos scenario (rate 180, faulted) is the
headline cell: its host materialization cost dominates the host loop.

Run:  PYTHONPATH=src python benchmarks/train_throughput.py --smoke
      PYTHONPATH=src python benchmarks/train_throughput.py
      HOST_DEVICES=8 benchmarks/run_hw.sh train_throughput --smoke \\
          --out results/train_throughput_smoke.json
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import PolicyConfig
from repro.core.policy import corais_init
from repro.core.train import (TemporalRLConfig, _cluster_seeds,
                              _element_keys, make_temporal_epoch_step,
                              make_temporal_train_step,
                              resolve_temporal_config)
from repro.optim import AdamConfig, adam_init
from repro.platform import setup_compile_cache
from repro.resilience import faults as faults_lib
from repro.serving import engine as engine_lib
from repro.serving.engine import EngineConfig
from repro.workloads import materialize_round_batch, scenario

REPORT_SCHEMA = "corais.train_throughput.v1"
HERE = os.path.dirname(os.path.abspath(__file__))
DEFAULT_OUT = os.path.join(HERE, "..", "results", "train_throughput.json")

_ARRIVAL_SALT = 0xA7
_FAULT_SEED_SALT = 0xFA


def build_cfg(name: str, *, batch_size: int, num_rounds: int,
              epoch_len: int) -> TemporalRLConfig:
    width = 64 if name.startswith("chaos") else 16
    return TemporalRLConfig(
        policy=PolicyConfig(d_model=32, ff_hidden=64, edge_layers=1,
                            request_layers=1, norm="layer"),
        engine=EngineConfig(num_edges=5, num_rounds=num_rounds,
                            max_per_round=width),
        scenario=name, batch_size=batch_size, lr=3e-4, seed=0,
        device_episodes=True, epoch_len=epoch_len)


def bench_host_loop(cfg: TemporalRLConfig, *, updates: int,
                    warmup: int) -> dict:
    """Pre-epoch trainer semantics: host episodes + per-batch sync."""
    cfg, fspec = resolve_temporal_config(cfg)
    ecfg = cfg.engine
    wl = scenario(cfg.scenario)
    key = jax.random.PRNGKey(cfg.seed)
    params, state = corais_init(jax.random.split(key)[1], cfg.policy)
    opt = adam_init(params, AdamConfig(lr=cfg.lr))
    step_fn, _ = make_temporal_train_step(cfg)

    def one(b, params, opt):
        sim0 = engine_lib.init_batch(ecfg, _cluster_seeds(cfg, b))
        arrivals = materialize_round_batch(
            wl, ecfg.num_edges, ecfg.num_rounds, ecfg.round_interval,
            cfg.batch_size,
            base_seed=int(np.random.default_rng(
                (cfg.seed, _ARRIVAL_SALT, b)).integers(0, 2**31 - 1)),
            max_per_round=ecfg.max_per_round, overflow="clip")
        if fspec is not None:
            arrivals = faults_lib.attach_fault_batch(
                arrivals, fspec, ecfg.num_edges,
                seeds=np.random.default_rng(
                    (cfg.seed, _FAULT_SEED_SALT, b)).integers(
                        0, 2**31 - 1, size=cfg.batch_size))
        skeys = _element_keys(key, b, cfg.batch_size)
        params, opt, metrics = step_fn(
            params, state, opt, jax.tree.map(jnp.asarray, sim0),
            jax.tree.map(jnp.asarray, arrivals), skeys)
        float(metrics["loss"])       # the per-batch blocking sync
        return params, opt

    for b in range(warmup):
        params, opt = one(b, params, opt)
    t0 = time.perf_counter()
    for b in range(warmup, warmup + updates):
        params, opt = one(b, params, opt)
    jax.block_until_ready(params)
    return {"wall_s": time.perf_counter() - t0, "updates": updates}


def bench_epoch(cfg: TemporalRLConfig, *, updates: int, warmup: int,
                mesh=None) -> dict:
    """Scanned-epoch path (optionally shard_map'd over ``mesh``)."""
    cfg, _ = resolve_temporal_config(cfg)
    ecfg = cfg.engine
    key = jax.random.PRNGKey(cfg.seed)
    params, state = corais_init(jax.random.split(key)[1], cfg.policy)
    opt = adam_init(params, AdamConfig(lr=cfg.lr))
    step_fn, _ = make_temporal_epoch_step(cfg, mesh=mesh)
    K = max(1, cfg.epoch_len)

    def chunk(b0, k, params, opt):
        bs = list(range(b0, b0 + k))
        stacks = [engine_lib.init_batch(ecfg, _cluster_seeds(cfg, bi))
                  for bi in bs]
        sim0 = {key_: jnp.asarray(np.stack([s[key_] for s in stacks]))
                for key_ in stacks[0]}
        ekeys = jnp.stack([_element_keys(key, bi, cfg.batch_size)
                           for bi in bs])
        params, opt, metrics = step_fn(params, state, opt, sim0, ekeys)
        return params, opt, metrics

    b = 0
    for _ in range(max(1, (warmup + K - 1) // K)):
        params, opt, metrics = chunk(b, K, params, opt)
        b += K
    jax.block_until_ready(params)
    n_chunks = (updates + K - 1) // K
    done = 0
    t0 = time.perf_counter()
    for _ in range(n_chunks):
        params, opt, metrics = chunk(b, K, params, opt)
        b += K
        done += K
    jax.block_until_ready((params, metrics))
    return {"wall_s": time.perf_counter() - t0, "updates": done}


def run_cell(mode: str, cfg: TemporalRLConfig, *, updates: int, warmup: int,
             mesh=None) -> dict:
    if mode == "host-loop":
        res = bench_host_loop(cfg, updates=updates, warmup=warmup)
    else:
        res = bench_epoch(cfg, updates=updates, warmup=warmup, mesh=mesh)
    bps = res["updates"] / res["wall_s"]
    return {
        "mode": mode, "scenario": cfg.scenario,
        "batch_size": cfg.batch_size, "num_rounds": cfg.engine.num_rounds,
        "epoch_len": max(1, cfg.epoch_len) if mode != "host-loop" else 1,
        "updates": res["updates"], "wall_s": round(res["wall_s"], 4),
        "batches_per_sec": round(bps, 4),
        "episode_rounds_per_sec": round(
            bps * cfg.batch_size * cfg.engine.num_rounds, 2),
    }


def main() -> int:
    setup_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--scenarios", default="uniform_iid,chaos-rolling-failure")
    ap.add_argument("--modes", default="host-loop,scan-epoch,sharded")
    ap.add_argument("--updates", type=int, default=24,
                    help="measured updates per (mode, scenario) cell")
    ap.add_argument("--warmup", type=int, default=4)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=12)
    ap.add_argument("--epoch-len", type=int, default=8)
    ap.add_argument("--smoke", action="store_true",
                    help="CI preset: fewer updates/rounds, same cell grid")
    ap.add_argument("--out", default=DEFAULT_OUT)
    args = ap.parse_args()
    if args.smoke:
        args.updates, args.warmup = 6, 2
        args.rounds, args.epoch_len = 6, 3

    modes = [m.strip() for m in args.modes.split(",") if m.strip()]
    mesh = None
    if "sharded" in modes:
        if len(jax.devices()) > 1:
            from repro.launch.mesh import make_fleet_mesh
            mesh = make_fleet_mesh()
            shards = int(np.prod(list(mesh.devices.shape)))
            if args.batch_size % shards:
                raise SystemExit(f"--batch-size {args.batch_size} must "
                                 f"divide over {shards} devices")
        else:
            print("note: single device visible — skipping 'sharded' "
                  "(use HOST_DEVICES=8 benchmarks/run_hw.sh ...)")
            modes = [m for m in modes if m != "sharded"]

    cells = []
    for name in [s.strip() for s in args.scenarios.split(",") if s.strip()]:
        cfg = build_cfg(name, batch_size=args.batch_size,
                        num_rounds=args.rounds, epoch_len=args.epoch_len)
        for mode in modes:
            cell = run_cell(mode, cfg, updates=args.updates,
                            warmup=args.warmup,
                            mesh=mesh if mode == "sharded" else None)
            cells.append(cell)
            print(f"  {mode:10s} {name:22s} "
                  f"{cell['batches_per_sec']:8.3f} batches/s "
                  f"{cell['episode_rounds_per_sec']:10.1f} ep-rounds/s "
                  f"({cell['updates']} updates in {cell['wall_s']:.2f}s)")
    by = {(c["scenario"],): {} for c in cells}
    for c in cells:
        by[(c["scenario"],)][c["mode"]] = c["batches_per_sec"]
    for (name,), d in by.items():
        if "host-loop" in d and "scan-epoch" in d:
            print(f"  scan-epoch speedup over host-loop ({name}): "
                  f"{d['scan-epoch'] / d['host-loop']:.2f}x")

    report = {
        "schema": REPORT_SCHEMA,
        "smoke": bool(args.smoke),
        "devices": len(jax.devices()),
        "backend": jax.default_backend(),
        "platform": platform.platform(),
        "cells": cells,
    }
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(f"report written to {os.path.abspath(args.out)}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
