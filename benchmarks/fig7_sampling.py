"""Paper Fig. 7 — sampling-decode effect: more samples -> better gap at a
small (vectorized) time cost."""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_line, eval_instances, get_trained_policy
from repro.core.decode import sampling_decode
from repro.core.heuristics import solve_ils
from repro.core.objective import makespan_np
from repro.core.policy import corais_apply
from repro.platform import setup_compile_cache


def main():
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--en", type=int, default=10)
    ap.add_argument("--rn", type=int, default=100)
    ap.add_argument("--instances", type=int, default=10)
    ap.add_argument("--batches", type=int, default=800)
    ap.add_argument("--samples", type=int, nargs="+",
                    default=[1, 10, 100, 1000])
    args = ap.parse_args()
    params, state, cfg = get_trained_policy(5, 50, args.batches)
    instances = eval_instances(args.en, args.rn, args.instances)
    refs = [makespan_np(i, solve_ils(i, budget_s=2.0, seed=0))
            for i in instances]

    @jax.jit
    def forward(jinst):
        lp, _ = corais_apply(params, state, jinst, cfg.policy, training=False)
        return lp

    for n in args.samples:
        decode = jax.jit(lambda jinst, lp, key, n=n:
                         sampling_decode(key, jinst, lp, n))
        gaps, times = [], []
        key = jax.random.PRNGKey(0)
        for inst, ref in zip(instances, refs):
            jinst = jax.tree.map(jnp.asarray, inst)
            lp = forward(jinst)
            key, sub = jax.random.split(key)
            jax.block_until_ready(decode(jinst, lp, sub))  # warm
            t0 = time.perf_counter()
            assign, _ = decode(jinst, lp, sub)
            assign = np.asarray(jax.block_until_ready(assign))
            times.append(time.perf_counter() - t0)
            gaps.append(makespan_np(inst, assign) / max(ref, 1e-9))
        print(csv_line(f"fig7/EN{args.en}_RN{args.rn}/samples_{n}",
                       float(np.mean(times)) * 1e6,
                       f"gap={float(np.mean(gaps)):.4f}"))


if __name__ == "__main__":
    main()
