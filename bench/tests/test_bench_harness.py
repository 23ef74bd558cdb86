"""The benchmark harness reads data: every cell's files are found by name,
a missing one fails and names itself, a new cell is files plus entries, and
each driver's window runs end to end on the CPU at a tiny width."""
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import pytest  # noqa: E402

from benchlib import harness  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
TINY_POLICY = {"d_model": 32, "num_heads": 4, "edge_layers": 2,
               "request_layers": 1, "ff_hidden": 64}
#: per driver: traffic overrides that make a window a fraction of a second
TINY_TRAFFIC = {
    "serve": {"rate_per_s": 20.0, "pool": 8, "check_rounds": 4,
              "warm_rounds": 2},
    "rollout": {"batch": 4, "pool_batches": 2, "check_instances": 2},
}
LINE_KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def tiny_run(name, seconds=0.3, trace=False, root=ROOT, tmp=None):
    cell = harness.find_cell(name, root)
    cell.traffic.update(TINY_TRAFFIC[cell.traffic["driver"]])
    ctx = harness.Context(cell=cell, seed=2**31 + 5, seconds=seconds,
                          trace=trace, t_process_start=time.monotonic(),
                          trace_dir=Path(tmp or "/nonexistent") / "trace",
                          policy_override=TINY_POLICY)
    out = cell.driver.run(ctx)
    dev = {"platform": "cpu", "kind": "cpu", "count": 1}
    return cell, out, harness.result_line(cell, out, dev)


def copy_tree(dst: Path) -> Path:
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(BENCH, dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return dst


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_found(name):
    cell = harness.find_cell(name)
    assert cell.config["name"] == next(
        w for w in SPEC["workloads"] if w["name"] == name)["config"]
    assert callable(cell.driver.run)
    assert "setup_s" in [m["name"] for m in cell.end_to_end]
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for spec, reader in cell.per_layer:
        assert callable(reader.read), spec["name"]
        assert spec["moves"] in [m["name"] for m in cell.end_to_end]


@pytest.mark.parametrize("kind", ["config", "traffic", "driver", "metric"])
def test_missing_file_fails_and_names_itself(tmp_path, kind):
    root = copy_tree(tmp_path)
    cell = harness.find_cell(CELLS[0], root)
    w = next(w for w in SPEC["workloads"] if w["name"] == CELLS[0])
    victim = {
        "config": root / next(c["file"] for c in SPEC["configs"]
                              if c["name"] == w["config"]),
        "traffic": root / "bench" / "traffic" / f"{w['traffic']}.json",
        "driver": root / "bench" / "drivers" / f"{cell.traffic['driver']}.py",
        "metric": root / "bench" / "metrics" / f"{cell.per_layer[0][0]['name']}.py",
    }[kind]
    victim.unlink()
    with pytest.raises(harness.MissingFile, match=str(victim.name).replace(
            ".", r"\.")):
        harness.find_cell(CELLS[0], root)


def test_new_cell_is_files_plus_entries(tmp_path):
    """A new configuration, traffic mix and per-layer metric are found with
    no edit to any file the benchmark already has."""
    root = copy_tree(tmp_path)
    b = root / "bench"
    cfg = json.loads((b / "configs" / "paper-10e.json").read_text())
    cfg["name"] = "paper-20e"
    (b / "configs" / "paper-20e.json").write_text(json.dumps(cfg))
    trf = json.loads((b / "traffic" / "serve-10e-poisson.json").read_text())
    trf.update(edges=20, rate_per_s=50.0)
    (b / "traffic" / "serve-20e-poisson.json").write_text(json.dumps(trf))
    (b / "metrics" / "fastpath.rounds.py").write_text(
        "def read(data):\n    return float(len(data['calls']))\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "paper-20e", "source": "x",
                            "file": "bench/configs/paper-20e.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "serve-paper-20e-poisson",
                              "config": "paper-20e",
                              "traffic": "serve-20e-poisson", "chips": 1,
                              "why": "x"})
    spec["per_layer"].append({"name": "fastpath.rounds", "unit": "rounds",
                              "better": "higher", "source": "host_clock",
                              "layer": "serving/fastpath",
                              "moves": "decision_p50_ms",
                              "workloads": ["serve-paper-20e-poisson"]})
    for m in spec["end_to_end"]:
        if "workloads" in m and "serve-paper-10e-poisson" in m["workloads"]:
            m["workloads"].append("serve-paper-20e-poisson")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.find_cell("serve-paper-20e-poisson", root)
    assert cell.config["name"] == "paper-20e" and cell.traffic["edges"] == 20
    names = [s["name"] for s, _ in cell.per_layer]
    assert "fastpath.rounds" in names
    reader = dict((s["name"], r) for s, r in cell.per_layer)["fastpath.rounds"]
    assert reader.read({"calls": [(20, 60)] * 3}) == 3.0


@pytest.mark.parametrize("name", CELLS)
def test_driver_window_runs_on_cpu(name):
    cell, out, line = tiny_run(name)
    assert list(line) == LINE_KEYS
    assert line["correct"] is True, line["checks"]
    # failed counts answers that never came or came malformed, not late ones
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    for k, v in line["checks"].items():
        assert v["value"] <= v["limit"], k


@pytest.mark.parametrize("name", CELLS)
def test_driver_traced_window_on_cpu(name, tmp_path):
    """With the profiler on, the line carries the per-layer metrics there is
    something to read for (on the CPU: no device plane, so none of the
    device's), and busy/window seconds."""
    cell, out, line = tiny_run(name, trace=True, tmp=tmp_path)
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) <= {s["name"] for s, _ in cell.per_layer}
    assert line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_serve_failed_counts_unanswered_or_malformed_rounds():
    """A round fails when its answer never comes or is not one edge in range
    for each request; how late an answer comes does not enter."""
    import numpy as np

    drv = harness.find_cell(CELLS[0]).driver
    inst = {"edge_mask": np.ones(3, bool), "req_mask": np.ones(4, bool)}
    ok = np.array([0, 1, 2, 0])
    rec = {"assign": [ok, None, ok[:2], np.array([0, 1, 3, 0]), ok]}
    assert drv.unanswered_or_malformed([inst], [0] * 5, rec) == 3


@pytest.mark.parametrize("late,rounds,want", [(0, 100, 0.0), (5, 100, 5.0),
                                              (0, 0, None)])
def test_late_share_reads_late_rounds(late, rounds, want):
    reader = dict((s["name"], r) for s, r in
                  harness.find_cell(CELLS[0]).per_layer)["late_share.serve"]
    assert reader.read({"late": late, "rounds": rounds}) == want


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, str(Path(cwd) / "bench" / "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    got = _run_py(ROOT)
    assert got.returncode == 2 and got.stdout == "", got.stderr
    assert "no TPU" in got.stderr


def test_run_refuses_with_only_the_benchmark_files(tmp_path):
    root = copy_tree(tmp_path)
    got = _run_py(root, {"PYTHONPATH": str(ROOT / "src")})
    assert got.returncode != 0 and got.stdout == ""


def test_rollout_window_traces_only_its_first_calls():
    """A whole window of the engine's scan steps overflows the profiler's
    event buffer, so the rollout stops the trace once ``trace_calls`` calls
    are done and goes on to the end of the window."""
    import numpy as np

    cell = harness.find_cell("rollout-paper-10e-policy")
    made, stops = [], []

    def run(*args):
        made.append(1)
        time.sleep(0.01)
        return {"completed": np.ones(3), "slot_finish": 0, "slot_edge": 0}, \
            {"assign": 0}

    _, calls = cell.driver.window(run, [{"dev": ()}], 0.1, trace_calls=2,
                                  stop_trace=lambda: stops.append(len(made)))
    assert stops == [2] and len(calls) == len(made) > 2
