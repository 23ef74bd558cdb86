#!/usr/bin/env python3
"""Find a serve cell's knee: the highest Poisson round rate at which the
backlog does not grow over the window and the p99 decision latency stays
under the traffic's lateness limit.

    python3 bench/sweep.py --workload <serve cell> --seed <n> --seconds <s> \
        --rates 200,400,800,...

One process, one set-up; each rate runs one open-loop window through the
cell's own driver. Prints one JSON line per rate and a closing line with
the knee. The cell's traffic file then takes half the knee as its rate
(at four fifths the tails swing run to run).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from benchlib import gen, harness  # noqa: E402


def sustained(row: dict, late_s: float) -> bool:
    """No growing backlog (the last quarter's median latency within twice
    the first quarter's, plus 1 ms) and the p99 under the limit."""
    return (row["failed"] == 0 and row["p99_ms"] < late_s * 1e3
            and row["q4_p50_ms"] <= 2 * row["q1_p50_ms"] + 1.0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    import numpy as np
    from repro import platform
    platform.setup_compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU; refusing to run", file=sys.stderr)
        return 2

    cell = harness.find_cell(args.workload)
    serve = cell.driver
    ctx = harness.Context(cell=cell, seed=args.seed, seconds=args.seconds,
                          trace=False, t_process_start=time.monotonic(),
                          trace_dir=BENCH.parent / ".bench_trace" / "sweep")
    _, _, _, pool, _, _, fp, _ = serve.setup(ctx)
    late = cell.traffic["late_after_s"]
    knee = None
    for rate in [float(r) for r in args.rates.split(",")]:
        due = gen.poisson_schedule(args.seed, rate, args.seconds)
        order = gen.pool_order(args.seed, len(pool), len(due))
        rec = serve.window(fp, pool, order, due, args.seconds, drain_s=10.0)
        lat = (rec["res1"] - rec["due"]) * 1e3
        ok = np.isfinite(lat)
        q = len(due) // 4
        row = {"rate": rate, "rounds": len(due), "answered": int(ok.sum()),
               "failed": int((~ok).sum() + (lat[ok] > late * 1e3).sum()),
               "p50_ms": float(np.percentile(lat[ok], 50)),
               "p99_ms": float(np.percentile(lat[ok], 99)),
               "q1_p50_ms": float(np.nanmedian(lat[:q])),
               "q4_p50_ms": float(np.nanmedian(lat[-q:])),
               "service_p50_ms": float(np.nanmedian(
                   (rec["res1"] - rec["sub0"]) * 1e3))}
        row["sustained"] = sustained(row, late)
        print(json.dumps(row), flush=True)
        if row["sustained"]:
            knee = rate
        else:
            break
    print(json.dumps({"workload": args.workload, "knee": knee,
                      "rate_at_4_5": None if knee is None else 0.8 * knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
