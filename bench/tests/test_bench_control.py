"""The control of each cell's comparison fails it: the plain reference
with float8 (e4m3) matmul operands (and, in the rollout, the physics in
bfloat16 times), put in the program's place, breaks at least one of
the cell's limits, while the program passes them all. Run here at the
cell's policy width on the CPU, with a window of a few rounds or calls;
bench/control.py reads the same numbers on the chip at the cell's own
size."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import pytest  # noqa: E402

import control  # noqa: E402
from benchlib import harness  # noqa: E402

#: per cell: traffic overrides that keep a CPU run to seconds
SMALL = {
    "serve-paper-10e-poisson": {"rate_per_s": 20.0, "pool": 16,
                                "check_rounds": 16, "warm_rounds": 1},
    "serve-metro-100e-poisson": {"rate_per_s": 10.0, "pool": 4,
                                 "check_rounds": 4, "warm_rounds": 1},
    "rollout-paper-10e-policy": {"batch": 4, "pool_batches": 1,
                                 "check_instances": 4},
}


@pytest.mark.parametrize("name", sorted(SMALL))
def test_control_fails_and_program_passes(name):
    cell = harness.find_cell(name)
    cell.traffic.update(SMALL[name])
    got = control.readings(cell, seed=2**31 + 17, seconds=0.4)
    limits = cell.traffic["limits"]
    prog, ctl = got["program"], got["control"]
    assert all(prog[k] <= v for k, v in limits.items()), (prog, limits)
    assert any(ctl[k] > v for k, v in limits.items()), (ctl, limits)
