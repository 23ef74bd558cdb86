"""Benchmark entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Default budget suits one CPU core
(~10-15 min incl. one cached policy training); ``--full`` expands to all
paper scales + ablations.
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    from repro.platform import setup_compile_cache

    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--batches", type=int, default=800)
    ap.add_argument("--skip-tables", action="store_true",
                    help="only roofline + latency (no policy training)")
    args = ap.parse_args()

    print("name,us_per_call,derived")
    t0 = time.time()

    if not args.skip_tables:
        from benchmarks import (fig7_sampling, latency_scheduler,
                                table2_conventional, table3_generalization,
                                table4_characteristics)
        scales = ([(5, 50), (10, 50), (5, 100), (10, 100)]
                  if args.full else [(5, 50)])
        for en, rn in scales:
            for row in table2_conventional.run(
                    en, rn, n_instances=20 if not args.full else 50,
                    batches=args.batches, include_ablations=args.full,
                    verbose=False):
                print(row)
        for row in table3_generalization.run(batches=args.batches,
                                             verbose=False):
            print(row)
        sys.argv = ["table4", "--batches", str(args.batches),
                    "--trials", "100"]
        table4_characteristics.main()
        sys.argv = ["fig7", "--batches", str(args.batches),
                    "--instances", "8"]
        fig7_sampling.main()
        sys.argv = ["latency", "--batches", str(args.batches)]
        latency_scheduler.main()

    from benchmarks import roofline_run
    sys.argv = ["roofline", "--csv"]
    roofline_run.main()

    print(f"# benchmarks completed in {time.time()-t0:.0f}s",
          file=sys.stderr)


if __name__ == "__main__":
    main()
