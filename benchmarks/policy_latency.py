"""Real-time decision latency of the unified policy inference stack.

The paper's headline claim is millisecond-level scheduling regardless of
system scale; this benchmark measures it directly, per decision *path*.
Cells (schema corais.policy_latency.v2) are keyed by
(backend, Q, Z, stage, decode):

  stage=decision — one full scheduling decision (encode + eq 16-17 head +
      greedy decode) through the compile-once serving path
      (``make_decision_fn``), for every score backend (``xla`` einsum head,
      ``ref`` pure-jnp oracle, ``pallas`` fused kernel — interpret mode
      off-TPU) and decode route:
        decode=host  — materialize the (Z, Q) log-probs, argmax
        decode=fused — argmax inside the scoring kernel; (Z, Q) is never
                       materialized (kernels/policy_score.py)
      Reports mean / p50 / p95 / p99 wall latency over ``--reps`` calls,
      one-off compile time, and (``--batch``) vmapped throughput.

  stage=head — the decode head in isolation (encoder outputs precomputed):
      the serving-loop cost the fused decode actually removes.
        decode=host  — pallas score kernel + device->host fetch of the
                       (Z, Q) matrix + np.argmax on the host
        decode=fused — fused decode kernel (k=1, unnormalized) + a (Z,)
                       int32 fetch
      The headline comparison: fused p95 must beat host p95 ~2x at the
      paper's top scale (Q=100, Z=1000) on the same machine.

``--fastpath`` additionally drives :class:`repro.serving.DecisionFastPath`
over every padding bucket against explicit p50/p95/p99 SLOs and writes the
pass/fail table to results/slo_report.json (uploaded as a CI artifact;
informational — the hard CI gate is check_latency_drift.py).

Run:  PYTHONPATH=src python benchmarks/policy_latency.py
      PYTHONPATH=src python benchmarks/policy_latency.py \\
          --backends xla,pallas --scales 10x100,100x1000 --batch 16
      PYTHONPATH=src python benchmarks/policy_latency.py --smoke --fastpath
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import InstanceConfig, generate_batch, generate_instance
from repro.core.inference import make_decision_fn, policy_decide
from repro.core.policy import (PolicyConfig, corais_encode, corais_init,
                               list_score_backends)
from repro.platform import interpret_mode, setup_compile_cache
from repro.serving.fastpath import (DEFAULT_BUCKETS, DecisionFastPath,
                                    SLOSpec, evaluate_slo)

REPORT_SCHEMA = "corais.policy_latency.v2"
SLO_SCHEMA = "corais.slo_report.v1"
#: paper scales and beyond: Table II tops out at Q=10, Z=100
DEFAULT_QS = (5, 10, 50, 100)
DEFAULT_ZS = (20, 100, 500, 1000)
#: default serving SLO (ms) for the fast-path section; override per run
DEFAULT_SLO = (25.0, 50.0, 100.0)


def _percentiles(times_s: list) -> dict:
    t = np.asarray(times_s) * 1e3
    return {
        "mean_ms": float(t.mean()),
        "p50_ms": float(np.percentile(t, 50)),
        "p95_ms": float(np.percentile(t, 95)),
        "p99_ms": float(np.percentile(t, 99)),
        "max_ms": float(t.max()),
    }


def bench_cell(params, state, pcfg: PolicyConfig, backend: str, q: int,
               z: int, *, decode: str = "host", batch: int, reps: int,
               seed: int = 999) -> dict:
    """One (backend, Q, Z, decision, decode) cell: single-decision latency
    + batched throughput on freshly generated instances of that scale."""
    fused = decode == "fused"
    rng = np.random.default_rng(seed)
    icfg = InstanceConfig(num_edges=q, num_requests=z)
    inst = jax.tree.map(jnp.asarray, generate_instance(rng, icfg))
    key = jax.random.PRNGKey(0)

    # the exact compile-once path the serving controller / fast path runs
    # (fused serving skips the argmax-invariant log-softmax normalizer)
    decide = make_decision_fn(params, state, pcfg, mode="greedy",
                              backend=backend, fused_decode=fused,
                              normalize=not fused)

    t0 = time.perf_counter()
    jax.block_until_ready(decide(inst, key))
    compile_s = time.perf_counter() - t0

    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(decide(inst, key))
        times.append(time.perf_counter() - t0)
    single = _percentiles(times)
    single["compile_s"] = compile_s

    cell = {"backend": backend, "num_edges": q, "num_requests": z,
            "stage": "decision", "decode": decode, "single": single}

    if batch > 0:
        binst = jax.tree.map(jnp.asarray, generate_batch(rng, icfg, batch))
        keys = jax.random.split(key, batch)
        vdecide = jax.jit(jax.vmap(
            lambda i, k: policy_decide(k, params, state, i, pcfg,
                                       mode="greedy", backend=backend,
                                       fused_decode=fused,
                                       normalize=not fused)))
        jax.block_until_ready(vdecide(binst, keys))  # compile
        btimes = []
        for _ in range(max(1, reps // 2)):
            t0 = time.perf_counter()
            jax.block_until_ready(vdecide(binst, keys))
            btimes.append(time.perf_counter() - t0)
        wall = float(np.mean(btimes))
        cell["batched"] = {
            "batch": batch,
            "wall_ms": wall * 1e3,
            "decisions_per_s": batch / wall,
            "requests_per_s": batch * z / wall,
        }
    return cell


def bench_head_cell(params, state, pcfg: PolicyConfig, q: int, z: int, *,
                    decode: str, reps: int, seed: int = 999) -> dict:
    """One (pallas, Q, Z, head, decode) cell: the decode head in isolation,
    encoder outputs precomputed and resident on device.

    host  = pallas score kernel -> fetch the full (Z, Q) matrix -> np.argmax
    fused = fused decode kernel -> fetch (Z,) winner indices

    Both ends with a host-side numpy assignment, because that is what the
    serving loop hands to dispatch — the fused row's win is the (Z, Q)
    materialization + transfer + host scan it never does."""
    from repro.kernels import ops

    rng = np.random.default_rng(seed)
    icfg = InstanceConfig(num_edges=q, num_requests=z)
    inst = jax.tree.map(jnp.asarray, generate_instance(rng, icfg))
    c, h, _ = corais_encode(params, state, inst, pcfg)
    c, h = jax.block_until_ready((c, h))
    wx, wy = params["w_px"], params["w_py"]
    mask = inst["edge_mask"]
    clip = pcfg.tanh_clip

    if decode == "host":
        def step():
            lp = ops.policy_score(c, h, wx, wy, mask, tanh_clip=clip)
            return np.argmax(np.asarray(lp), axis=-1)
    else:
        def step():
            ti, _ = ops.policy_score_decode(c, h, wx, wy, mask,
                                            tanh_clip=clip, k=1,
                                            normalize=False)
            return np.asarray(ti)[:, 0]

    t0 = time.perf_counter()
    step()
    compile_s = time.perf_counter() - t0
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        step()
        times.append(time.perf_counter() - t0)
    single = _percentiles(times)
    single["compile_s"] = compile_s
    return {"backend": "pallas", "num_edges": q, "num_requests": z,
            "stage": "head", "decode": decode, "single": single}


def _fmt_cell(cell: dict) -> str:
    s = cell["single"]
    line = (f"  {cell['backend']:7s} {cell['stage']:8s} "
            f"{cell['decode']:5s} Q={cell['num_edges']:4d} "
            f"Z={cell['num_requests']:5d} mean={s['mean_ms']:8.3f}ms "
            f"p95={s['p95_ms']:8.3f}ms p99={s['p99_ms']:8.3f}ms")
    b = cell.get("batched")
    if b:
        line += (f"  batched[{b['batch']}]={b['decisions_per_s']:8.1f} dec/s "
                 f"{b['requests_per_s']:10.0f} req/s")
    return line


def run(backends, scales, *, d_model: int, batch: int, reps: int,
        decodes=("host", "fused"), head_scales=(), seed: int = 0,
        verbose: bool = True) -> dict:
    pcfg = PolicyConfig(d_model=d_model)
    params, state = corais_init(jax.random.PRNGKey(seed), pcfg)
    cells = []
    for backend in backends:
        for q, z in scales:
            for decode in decodes:
                cell = bench_cell(params, state, pcfg, backend, q, z,
                                  decode=decode, batch=batch, reps=reps)
                cells.append(cell)
                if verbose:
                    print(_fmt_cell(cell))
    for q, z in head_scales:
        for decode in ("host", "fused"):
            cell = bench_head_cell(params, state, pcfg, q, z, decode=decode,
                                   reps=reps)
            cells.append(cell)
            if verbose:
                print(_fmt_cell(cell))
    return {
        "schema": REPORT_SCHEMA,
        "config": {
            "backends": list(backends),
            "scales": [list(s) for s in scales],
            "head_scales": [list(s) for s in head_scales],
            "decodes": list(decodes),
            "d_model": d_model, "batch": batch, "reps": reps,
            "device": jax.devices()[0].platform,
            "pallas_interpret": interpret_mode(),
        },
        "cells": cells,
    }


def run_fastpath(*, d_model: int, reps: int, slo: SLOSpec,
                 buckets=DEFAULT_BUCKETS, seed: int = 0,
                 verbose: bool = True) -> dict:
    """Drive the online fast path over every padding bucket against the SLO
    contract; returns the corais.slo_report.v1 payload."""
    pcfg = PolicyConfig(d_model=d_model)
    params, state = corais_init(jax.random.PRNGKey(seed), pcfg)
    paths = []
    for bq, bz in buckets:
        fp = DecisionFastPath(params, state, pcfg, buckets=((bq, bz),))
        fp.warmup()
        rng_seed = 1000 + bq
        insts = [
            {k: np.asarray(v) for k, v in generate_instance(
                np.random.default_rng(rng_seed + i),
                InstanceConfig(num_edges=bq, num_requests=bz)).items()}
            for i in range(max(3, reps))
        ]
        spec = SLOSpec(slo.p50_ms, slo.p95_ms, slo.p99_ms,
                       name=f"fastpath-{bq}x{bz}")
        report = evaluate_slo(fp, insts, spec)
        paths.append(report)
        if verbose:
            mark = "PASS" if report["pass"] else "FAIL"
            print(f"  fastpath Q={bq:4d} Z={bz:5d} "
                  f"p50={report['p50_ms']:8.3f}/{spec.p50_ms:g}ms "
                  f"p95={report['p95_ms']:8.3f}/{spec.p95_ms:g}ms "
                  f"p99={report['p99_ms']:8.3f}/{spec.p99_ms:g}ms  {mark}")
    return {
        "schema": SLO_SCHEMA,
        "config": {
            "d_model": d_model, "reps": reps,
            "slo_ms": {"p50": slo.p50_ms, "p95": slo.p95_ms,
                       "p99": slo.p99_ms},
            "buckets": [list(b) for b in buckets],
            "device": jax.devices()[0].platform,
            "pallas_interpret": interpret_mode(),
        },
        "paths": paths,
        "pass": all(p["pass"] for p in paths),
    }


def main() -> None:
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--backends", default="xla,ref,pallas",
                    help=f"comma list from: {','.join(list_score_backends())}")
    ap.add_argument("--scales", default=None,
                    help="comma list of QxZ (default: full paper matrix "
                         f"{'x'.join(map(str, DEFAULT_QS))} x "
                         f"{'x'.join(map(str, DEFAULT_ZS))})")
    ap.add_argument("--head-scales", default="100x1000",
                    help="comma list of QxZ for isolated head cells "
                         "('' disables)")
    ap.add_argument("--decodes", default="host,fused",
                    help="decision decode routes to time")
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8,
                    help="batched-throughput width (0 disables)")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--fastpath", action="store_true",
                    help="also drive the serving fast path against SLOs "
                         "and write results/slo_report.json")
    ap.add_argument("--slo", default=",".join(map(str, DEFAULT_SLO)),
                    help="fast-path SLO as p50,p95,p99 in ms")
    ap.add_argument("--smoke", action="store_true",
                    help="CI cell: tiny model, small scales, all backends")
    ap.add_argument("--out", default=None,
                    help="report path (default results/policy_latency.json)")
    ap.add_argument("--slo-out", default=None,
                    help="SLO report path (default results/slo_report.json)")
    args = ap.parse_args()

    if args.smoke:
        backends = list_score_backends()
        scales = [(5, 20), (10, 50)]
        head_scales = [(10, 50)]
        buckets = ((5, 20), (10, 50))
        d_model, batch, reps = 32, 4, 3
    else:
        backends = args.backends.split(",")
        if args.scales:
            scales = [tuple(map(int, s.split("x")))
                      for s in args.scales.split(",")]
        else:
            scales = [(q, z) for q in DEFAULT_QS for z in DEFAULT_ZS]
        head_scales = ([tuple(map(int, s.split("x")))
                        for s in args.head_scales.split(",")]
                       if args.head_scales else [])
        buckets = DEFAULT_BUCKETS
        d_model, batch, reps = args.d_model, args.batch, args.reps
    decodes = tuple(args.decodes.split(","))

    print(f"== policy decision latency: {len(backends)} backends x "
          f"{len(scales)} scales x {len(decodes)} decodes "
          f"(d_model={d_model}) ==")
    report = run(backends, scales, d_model=d_model, batch=batch, reps=reps,
                 decodes=decodes, head_scales=head_scales)

    out = args.out or os.path.join(os.path.dirname(__file__), "..",
                                   "results", "policy_latency.json")
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(out, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
    print(f"== report written to {os.path.abspath(out)} ==")

    if args.fastpath:
        p50, p95, p99 = (float(x) for x in args.slo.split(","))
        print(f"== serving fast path vs SLO p50<{p50:g}ms p95<{p95:g}ms "
              f"p99<{p99:g}ms ==")
        slo_report = run_fastpath(d_model=d_model, reps=reps,
                                  slo=SLOSpec(p50, p95, p99),
                                  buckets=buckets)
        slo_out = args.slo_out or os.path.join(
            os.path.dirname(__file__), "..", "results", "slo_report.json")
        os.makedirs(os.path.dirname(os.path.abspath(slo_out)), exist_ok=True)
        with open(slo_out, "w") as f:
            json.dump(slo_report, f, indent=2, sort_keys=True)
        print(f"== SLO report ({'PASS' if slo_report['pass'] else 'FAIL'}) "
              f"written to {os.path.abspath(slo_out)} ==")


if __name__ == "__main__":
    main()
