#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print the result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic, driver and per-layer metric readers are
found by name from BENCHMARK.json (bench/benchlib/harness.py). With
``--trace 0`` the line carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics read from a profiler trace of the
window. Refuses to run (exit 2, no result line) without a TPU, with fewer
chips than the cell asks for, or without the scheduler's sources in the
checkout. The numbers compared for ``correct`` are printed last on stderr
and, under ``checks``, last in the line.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from benchlib import harness  # noqa: E402


def process_start() -> float:
    """time.monotonic() at which this process started (Linux: its start
    time since boot, on the boot clock), else the top of this file."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age = time.clock_gettime(time.CLOCK_BOOTTIME) - ticks / os.sysconf(
            "SC_CLK_TCK")
        return min(T_START, time.monotonic() - age)
    except (OSError, ValueError, IndexError):
        return T_START


def fail(msg: str) -> int:
    print(f"bench/run.py: {msg}", file=sys.stderr)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the raw profiler trace to this directory")
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        return fail("--seed must be >= 0 and --seconds > 0")

    try:
        cell = harness.find_cell(args.workload, ROOT)
    except (KeyError, harness.MissingFile) as e:
        return fail(str(e))

    t_start = process_start()
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro import platform
    except ImportError as e:
        return fail(f"cannot import the scheduler from {ROOT / 'src'}: {e}")
    if (ROOT / "src") not in Path(platform.__file__).resolve().parents:
        return fail(f"the scheduler was imported from {platform.__file__}, "
                    f"not from this checkout's src/")
    platform.setup_compile_cache()
    import jax

    # every program goes into the persistent cache, small ones too, so
    # only a checkout's first run of a cell compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    t_imported = time.monotonic()
    devices = jax.devices()
    t_backend = time.monotonic()
    # the process's first device program: what the runtime spends on its
    # first execution, apart from what the cell's own set-up does
    jax.block_until_ready(jax.jit(lambda x: x + 1)(0))
    phases = {"interpreter": T_START - t_start,
              "imports": t_imported - T_START,
              "backend": t_backend - t_imported,
              "first_program": time.monotonic() - t_backend}
    if devices[0].platform != "tpu":
        return fail(f"no TPU found (JAX sees {devices[0].platform}); "
                    f"refusing to run")
    if len(devices) < cell.chips:
        return fail(f"cell {cell.name} needs {cell.chips} chips, JAX sees "
                    f"{len(devices)}")

    trace_dir = ROOT / ".bench_trace" / cell.name
    shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = harness.Context(cell=cell, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace),
                          t_process_start=t_start,
                          trace_dir=trace_dir, phases=phases)
    try:
        out = cell.driver.run(ctx)
    finally:
        if args.keep_trace and trace_dir.exists():
            shutil.copytree(trace_dir, args.keep_trace, dirs_exist_ok=True)
        shutil.rmtree(trace_dir, ignore_errors=True)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": cell.chips}
    line = harness.result_line(cell, out, device)
    print(harness.phase_line(ctx.phases), file=sys.stderr)
    for text in harness.check_lines(out.checks):
        print(text, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
