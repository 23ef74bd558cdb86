"""Paper Table IV / Figs 8-10 — characteristic validation.

LB (load balancing): homogeneous edges, equal backlogs, all requests at
edge A -> expect near-equal per-edge request counts.
WP (workload perception): homogeneous edges, edge A has the largest
backlog -> expect n_A smallest.
HA (heterogeneity awareness): heterogeneous speeds E>D>C>B>A with equalized
backlog response times -> expect faster edges serve more.

Reports per-edge EReqN (mean executed requests) and LCost (mean response
time of that edge) over many sampled decisions from the trained policy.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_line, get_trained_policy
from repro.core.decode import sampling_decode
from repro.core.objective import per_edge_times
from repro.core.policy import corais_apply
from repro.platform import setup_compile_cache


def _base_instance(q=5, z=50):
    coords = np.stack([np.linspace(0.1, 0.9, q), np.full(q, 0.5)], -1)
    w = np.linalg.norm(coords[:, None] - coords[None], axis=-1)
    return {
        "edge_coords": coords.astype(np.float32),
        "phi": np.tile(np.array([[0.5, 0.05]], np.float32), (q, 1)),
        "replicas": np.full(q, 2.0, np.float32),
        "workload": np.zeros((q, 3), np.float32),
        "w": w.astype(np.float32),
        "ct": np.float32(1.0),
        "req_src": np.zeros(z, np.int32),  # all submitted to edge A
        "req_size": np.full(z, 0.5, np.float32),
        "edge_mask": np.ones(q, bool),
        "req_mask": np.ones(z, bool),
    }


def scenario(kind: str, q=5, z=50):
    inst = _base_instance(q, z)
    if kind == "LB":
        inst["workload"][:, 0] = 2.0  # same backlogs everywhere
    elif kind == "WP":
        # same hardware, edge A much more loaded
        inst["workload"][:, 0] = np.linspace(4.0, 1.0, q)
    elif kind == "HA":
        # speeds E > D > C > B > A; backlog response times equalized
        speeds = np.linspace(1.0, 0.2, q)  # phi slope: smaller = faster
        inst["phi"] = np.stack([speeds, np.full(q, 0.02)], -1).astype(np.float32)
        inst["workload"][:, 0] = 2.0
    return inst


def run(kind: str, params, state, pcfg, trials=200, sample_n=128, z=50):
    inst = scenario(kind, z=z)
    jinst = jax.tree.map(jnp.asarray, inst)
    lp, _ = corais_apply(params, state, jinst, pcfg, training=False)
    counts = np.zeros(5)
    costs = np.zeros(5)
    key = jax.random.PRNGKey(0)

    @jax.jit
    def one(key):
        assign, _ = sampling_decode(key, jinst, lp, sample_n)
        t = per_edge_times(jinst, assign)["T"]
        cnt = jnp.sum(jax.nn.one_hot(assign, 5), axis=0)
        return cnt, t

    for _ in range(trials):
        key, sub = jax.random.split(key)
        cnt, t = one(sub)
        counts += np.asarray(cnt)
        costs += np.asarray(t)
    return counts / trials, costs / trials


def main():
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--trials", type=int, default=100)
    ap.add_argument("--batches", type=int, default=800)
    args = ap.parse_args()
    params, state, cfg = get_trained_policy(5, 50, args.batches)
    for kind in ("LB", "WP", "HA"):
        ereqn, lcost = run(kind, params, state, cfg.policy, trials=args.trials)
        for i, label in enumerate("ABCDE"):
            print(csv_line(f"table4/{kind}/edge_{label}", 0.0,
                           f"EReqN={ereqn[i]:.2f};LCost={lcost[i]:.3f}"))


if __name__ == "__main__":
    main()
