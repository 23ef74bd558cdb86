"""Each cell's comparison catches the faults its timed path can have: the
harness's look for a chip is skipped, the rest of a run is driven on the
CPU at a tiny width with the timed path broken underneath, and ``correct``
must come out false."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from test_bench_harness import CELLS, tiny_run  # noqa: E402
from benchlib import harness  # noqa: E402


def _driver(name):
    return harness.find_cell(name).traffic["driver"]


# -- serve: the fast path's answers ------------------------------------------

def _serve_altered(monkeypatch):
    """An answer altered where it is produced: every round's first request
    sent to the next edge."""
    from repro.serving.fastpath import DecisionFastPath
    orig = DecisionFastPath.result

    def result(self, handle):
        a = orig(self, handle).copy()
        a[0] = (a[0] + 1) % self.buckets[0][0]  # every bucket holds >= q edges
        return a

    monkeypatch.setattr(DecisionFastPath, "result", result)


def _serve_half(monkeypatch):
    """Half of each round's requests left out of the answer."""
    from repro.serving.fastpath import DecisionFastPath
    orig = DecisionFastPath.result
    monkeypatch.setattr(DecisionFastPath, "result",
                        lambda self, h: orig(self, h)[: h[1] // 2])


# -- rollout: the engine's calls ---------------------------------------------

def _rollout_wrap(monkeypatch, post):
    from repro.serving import engine
    orig = engine.make_rollout

    def make_rollout(cfg, assign_fn, **kw):
        run = orig(cfg, assign_fn, **kw)
        return lambda state, arr, key: post(state, *run(state, arr, key))

    monkeypatch.setattr(engine, "make_rollout", make_rollout)


def _rollout_unchanged(monkeypatch):
    """A step that returns its state unchanged."""
    _rollout_wrap(monkeypatch, lambda s0, final, infos: (
        {k: jnp.asarray(v) for k, v in s0.items()}, infos))


def _rollout_half(monkeypatch):
    """Half of the batch left out: its instances come back as they went in."""
    def post(s0, final, infos):
        half = len(final["completed"]) // 2
        return ({k: v.at[half:].set(jnp.asarray(s0[k])[half:])
                 for k, v in final.items()}, infos)
    _rollout_wrap(monkeypatch, post)


def _rollout_altered(monkeypatch):
    """A decision altered where it is produced: each round's first arrival
    sent to the next edge (the engine then runs what it was told)."""
    from repro.serving import engine
    orig = engine.resolve_assign_fn

    def resolve(name, **kw):
        fn = orig(name, **kw)

        def altered(key, inst):
            a = fn(key, inst)
            q = inst["edge_mask"].shape[-1]
            return a.at[0].set((a[0] + 1) % q)
        return altered

    monkeypatch.setattr(engine, "resolve_assign_fn", resolve)


FAULTS = {
    "serve": {"answer_altered": _serve_altered, "half_left_out": _serve_half},
    "rollout": {"state_unchanged": _rollout_unchanged,
                "half_left_out": _rollout_half,
                "answer_altered": _rollout_altered},
}
CASES = [(c, f) for c in CELLS for f in FAULTS[_driver(c)]]


@pytest.mark.parametrize("name,fault", CASES)
def test_fault_makes_correct_false(name, fault, monkeypatch):
    FAULTS[_driver(name)][fault](monkeypatch)
    if _driver(name) == "rollout":  # check every instance of every call
        from test_bench_harness import TINY_TRAFFIC
        monkeypatch.setitem(TINY_TRAFFIC["rollout"], "check_instances", 10**6)
    _, out, line = tiny_run(name)
    assert line["correct"] is False, line["checks"]
    failing = [k for k, v in line["checks"].items() if v["value"] > v["limit"]]
    assert failing, line["checks"]
    assert np.isfinite(line["attempted"])
