"""Scheduler real-time latency (paper Table II Time column): wall time of
one full scheduling decision (policy forward + greedy decode) across system
scales, on this host's CPU. Includes the fused policy_score kernel micro-
benchmark (interpret mode on CPU = correctness path, not TPU timing)."""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_line, eval_instances, get_trained_policy
from repro.core.decode import greedy_decode
from repro.core.policy import corais_apply
from repro.platform import setup_compile_cache


def main():
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", type=int, default=800)
    ap.add_argument("--scales", type=str, default="5x50,10x100,30x400,50x800")
    args = ap.parse_args()
    params, state, cfg = get_trained_policy(5, 50, args.batches)

    for scale in args.scales.split(","):
        en, rn = map(int, scale.split("x"))
        inst = eval_instances(en, rn, 1)[0]
        jinst = jax.tree.map(jnp.asarray, inst)

        @jax.jit
        def decide(jinst):
            lp, _ = corais_apply(params, state, jinst, cfg.policy,
                                 training=False)
            return greedy_decode(lp)

        jax.block_until_ready(decide(jinst))  # compile
        reps = 20
        t0 = time.perf_counter()
        for _ in range(reps):
            out = decide(jinst)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / reps
        print(csv_line(f"latency/decision_EN{en}_RN{rn}", dt * 1e6,
                       f"ms={dt*1e3:.3f}"))


if __name__ == "__main__":
    main()
