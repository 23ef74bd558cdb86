"""Paper Table III — generalization: a policy trained on a small scale is
applied, unchanged, to larger systems. The padded-instance design means the
same jitted forward serves any (EN, RN) below the pad."""
from __future__ import annotations

import argparse

from benchmarks.common import csv_line, eval_instances, get_trained_policy
from repro.core.evaluate import evaluate_methods, standard_method_suite
from repro.platform import setup_compile_cache


def run(train_scale=(5, 50), test_scales=((10, 100), (15, 150)),
        n_instances=10, batches=800, ref_budget=2.0, verbose=True):
    params, state, cfg = get_trained_policy(*train_scale, batches,
                                            verbose=verbose)
    rows = []
    for en, rn in test_scales:
        instances = eval_instances(en, rn, n_instances)
        methods = standard_method_suite(params, state, cfg.policy,
                                        ref_budget_s=ref_budget,
                                        random_ns=(100,),
                                        sample_ns=(1000,))
        ref = f"ILS({ref_budget}s)"
        results = evaluate_methods(instances, methods, reference=ref)
        for name, r in results.items():
            rows.append(csv_line(
                f"table3/train{train_scale[0]}x{train_scale[1]}"
                f"/test{en}x{rn}/{name}",
                r.mean_time_s * 1e6,
                f"gap={r.mean_gap:.4f};cost={r.mean_cost:.4f}"))
    return rows


def main():
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--instances", type=int, default=10)
    ap.add_argument("--batches", type=int, default=800)
    args = ap.parse_args()
    for row in run(n_instances=args.instances, batches=args.batches):
        print(row)


if __name__ == "__main__":
    main()
