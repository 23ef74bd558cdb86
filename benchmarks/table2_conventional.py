"""Paper Table II — conventional test: methods on the training scale.

Gap is relative to the strongest offline reference available in this
container (ILS with a wall-clock budget; Gurobi replaced — DESIGN.md §3).
Output: one CSV row per method: name,us_per_call,derived(gap etc).
"""
from __future__ import annotations

import argparse

import numpy as np

from benchmarks.common import csv_line, eval_instances, get_trained_policy
from repro.core.evaluate import evaluate_methods, standard_method_suite
from repro.core.policy import PolicyConfig
from repro.platform import setup_compile_cache


def run(en=5, rn=50, n_instances=20, batches=800, ref_budget=1.0,
        sample_ns=(100, 1000), include_ablations=False, verbose=True):
    params, state, cfg = get_trained_policy(en, rn, batches, verbose=verbose)
    instances = eval_instances(en, rn, n_instances)
    methods = standard_method_suite(params, state, cfg.policy,
                                    ref_budget_s=ref_budget,
                                    sample_ns=sample_ns)
    if include_ablations:
        from benchmarks.common import rl_config
        from repro.core.ablations import variant_config
        from repro.core.evaluate import _policy_method
        from repro.core.train import train
        for variant in ("fc1", "fc2", "fc3"):
            vcfg = rl_config(en, rn, batches)
            vcfg = type(vcfg)(**{**vcfg.__dict__,
                                 "policy": variant_config(vcfg.policy, variant)})
            vp, vs, _, _ = train(vcfg)
            methods[f"{variant.upper()}-CoRaiS(greedy)"] = _policy_method(
                vp, vs, vcfg.policy, "greedy", 0, seed=0)
    ref = f"ILS({ref_budget}s)"
    results = evaluate_methods(instances, methods, reference=ref)
    rows = []
    for name, r in results.items():
        rows.append(csv_line(
            f"table2/EN{en}_RN{rn}/{name}", r.mean_time_s * 1e6,
            f"gap={r.mean_gap:.4f};cost={r.mean_cost:.4f}"))
    return rows


def main():
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="all four paper scales + ablations")
    ap.add_argument("--instances", type=int, default=20)
    ap.add_argument("--batches", type=int, default=800)
    args = ap.parse_args()
    scales = [(5, 50), (10, 50), (5, 100), (10, 100)] if args.full else [(5, 50)]
    for en, rn in scales:
        for row in run(en, rn, args.instances, args.batches,
                       include_ablations=args.full):
            print(row)


if __name__ == "__main__":
    main()
