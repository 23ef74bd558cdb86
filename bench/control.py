#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--seconds 2]

For each seed, in one process: the cell's own set-up and a short window at
the cell's load, then every number ``correct`` compares, read twice: for
the program, and for the control, the plain reference with float8 (e4m3)
matmul operands put in the program's place (the rollout's physics in
bfloat16 times; the driver's ``control_readings``). Each limit
lies between the program's largest reading and the control's smallest. Not
run by the benchmark's own runs; bench/tests/test_bench_control.py keeps it
at a size a test run holds.
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

from benchlib import harness  # noqa: E402


def readings(cell, seed: int, seconds: float, policy_override=None) -> dict:
    """Program and control readings of one seed (any platform)."""
    ctx = harness.Context(cell=cell, seed=seed, seconds=seconds, trace=False,
                          t_process_start=time.monotonic(),
                          trace_dir=BENCH.parent / ".bench_trace" / "control",
                          policy_override=policy_override)
    return dict(seed=seed, **cell.driver.control_readings(ctx))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)

    from repro import platform
    platform.setup_compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU; refusing to run", file=sys.stderr)
        return 2
    cell = harness.find_cell(args.workload)
    rows = []
    for s in args.seeds.split(","):
        rows.append(readings(cell, int(s), args.seconds))
        print(json.dumps(rows[-1]), flush=True)
    keys = [k for k, v in rows[0]["program"].items()
            if isinstance(v, float)]
    print(json.dumps({"workload": args.workload, "seeds": len(rows),
                      "program_max": {k: max(r["program"][k] for r in rows)
                                      for k in keys},
                      "control_min": {k: min(r["control"][k] for r in rows)
                                      for k in keys}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
