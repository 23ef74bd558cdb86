"""Finds a cell's files by the names in BENCHMARK.json and assembles the
result line. Adding a configuration, traffic mix, driver or per-layer
metric is adding files and entries; nothing here names one.

    configs/<config>.json     the configuration's ``file`` entry
    traffic/<traffic>.json    parameters of the mix; its ``driver`` key names
    drivers/<driver>.py       the window; ``run(ctx) -> Outcome``
    metrics/<metric>.py       one per per-layer metric; ``read(data)``
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import sys
import time
from pathlib import Path
from typing import Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


class MissingFile(FileNotFoundError):
    """A file that BENCHMARK.json implies is not there."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    driver: object
    end_to_end: list        # specs of the end-to-end metrics it reports
    per_layer: list         # [(spec, reader module)]


@dataclasses.dataclass
class Context:
    """What a driver's ``run`` gets."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_process_start: float  # time.monotonic() at process start
    trace_dir: Path
    policy_override: Optional[dict] = None   # tests: a tiny width
    phases: dict = dataclasses.field(default_factory=dict)  # set-up, s


@dataclasses.dataclass
class Outcome:
    """What a driver's ``run`` returns."""
    correct: bool
    attempted: int
    failed: int
    end_to_end: dict        # name -> value (setup_s included)
    checks: dict            # name -> {"value": x, "limit": y}
    memory_peak_bytes: int
    layer_data: dict        # what the per-layer readers read
    busy_s: Optional[float] = None
    window_s: Optional[float] = None
    breakdown: Optional[dict] = None


def load_json(path: Path) -> dict:
    if not path.is_file():
        raise MissingFile(f"missing benchmark file: {path}")
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a Python file by path (its name may hold dots and dashes)."""
    if not path.is_file():
        raise MissingFile(f"missing benchmark file: {path}")
    path = path.resolve()
    name = "bench_file_" + "".join(c if c.isalnum() else "_" for c in str(path))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _applies(spec: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in spec:
        return cell in spec["workloads"]
    return spec.get("moves", None) in e2e_names if "moves" in spec else True


def find_cell(name: str, root: Path = ROOT) -> Cell:
    """Everything a run of cell ``name`` needs, found by name; raises
    KeyError for an unknown cell and MissingFile for a missing file."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    driver = load_module(root / "bench" / "drivers" / f"{traffic['driver']}.py")
    e2e = [m for m in bench["end_to_end"] if _applies(m, name, set())]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [(m, load_module(root / "bench" / "metrics" / f"{m['name']}.py"))
                 for m in bench["per_layer"] if _applies(m, name, e2e_names)]
    return Cell(name, int(w["chips"]), config, traffic, driver, e2e, per_layer)


def result_line(cell: Cell, out: Outcome, device: dict) -> dict:
    """The contract's last line: end-to-end metrics without tracing,
    per-layer metrics with it; ``checks`` comes last."""
    if out.busy_s is None:
        metrics = {m["name"]: {"value": out.end_to_end[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    else:
        metrics = {}
        for spec, reader in cell.per_layer:
            v = reader.read(out.layer_data)
            if v is not None:
                metrics[spec["name"]] = {"value": v, "unit": spec["unit"]}
    dev = dict(device, memory_peak_bytes=int(out.memory_peak_bytes))
    line = {"correct": bool(out.correct), "attempted": int(out.attempted),
            "failed": int(out.failed), "metrics": metrics, "device": dev}
    if out.busy_s is not None:
        dev["busy_s"] = out.busy_s
        dev["window_s"] = out.window_s
        if out.breakdown is not None:
            line["breakdown"] = out.breakdown
    line["checks"] = out.checks
    return line


def check_lines(checks: dict) -> list[str]:
    return [f"check {k}: {v['value']!r} limit {v['limit']!r} "
            f"{'ok' if v['value'] <= v['limit'] else 'FAIL'}"
            for k, v in checks.items()]


class CompileCounter:
    """Counts traces and backend compiles while open (JAX's monitoring
    events): the window should have none."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __enter__(self):
        import jax.monitoring

        self.count = 0
        self._on = True

        def listen(name, secs, **kw):
            if self._on and name in self.EVENTS:
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(listen)
        self._listen = listen
        return self

    def __exit__(self, *exc):
        import jax.monitoring

        self._on = False
        jax.monitoring.unregister_event_duration_listener(self._listen)
        return False


@contextlib.contextmanager
def phase(ctx: Context, name: str):
    """Adds the host seconds of one set-up step to ``ctx.phases`` under
    ``name``: what set-up spends its time on, printed on stderr."""
    t = time.monotonic()
    try:
        yield
    finally:
        ctx.phases[name] = ctx.phases.get(name, 0.0) + time.monotonic() - t


def phase_line(phases: dict) -> str:
    return "setup: " + " ".join(f"{k}_s={v:.4f}" for k, v in phases.items())


def span(on: bool, name: str):
    """A host span ``name`` in the profiler's trace while tracing, else
    nothing (end-to-end runs carry no annotations)."""
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)
