"""Host spans and kernel names a profiler trace shows: the ``corais.*``
spans of the decision fast path (``repro.tracing.span``), recorded on the
CPU and read back from the trace file, and the explicit names of the
policy's Pallas kernels, which name their operations in a TPU trace."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import InstanceConfig, generate_instance
from repro.core.policy import PolicyConfig, corais_init
from repro.kernels.policy_score import (policy_score_decode_fwd,
                                        policy_score_fwd)
from repro.serving.fastpath import (DecisionFastPath, PackedLayout,
                                    pad_instance)
from repro.tracing import span

CFG = PolicyConfig(d_model=32, ff_hidden=64, edge_layers=2, request_layers=1)
SUBMIT_STEPS = ("stage", "transfer", "dispatch")
RESULT_STEPS = ("wait", "fetch")


def _host_spans(trace_dir):
    """[(name, start_ns, end_ns, thread, args)] of every corais.* event on
    the host planes of the trace under ``trace_dir``."""
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("corais."):
                    s = int(ev.start_ns)
                    out.append((ev.name, s, s + int(ev.duration_ns),
                                (plane.name, line.name), dict(ev.stats)))
    return sorted(out, key=lambda e: e[1])


def _inst(q, z, seed):
    return {k: np.asarray(v) for k, v in generate_instance(
        np.random.default_rng(seed),
        InstanceConfig(num_edges=q, num_requests=z)).items()}


def test_span_names_and_arguments(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with span("unit.outer", round=7, q=3):
            with span("unit.inner"):
                pass
    got = {e[0]: e for e in _host_spans(str(tmp_path))}
    assert set(got) == {"corais.unit.outer", "corais.unit.inner"}
    outer, inner = got["corais.unit.outer"], got["corais.unit.inner"]
    assert outer[4] == {"round": 7, "q": 3} and inner[4] == {}
    assert outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.fixture(scope="module")
def traced_decisions(tmp_path_factory):
    """Five decisions through a warmed fast path under the profiler: four
    pipelined by ``stream`` (two buckets), one by ``decide``."""
    params, state = corais_init(jax.random.PRNGKey(0), CFG)
    fp = DecisionFastPath(params, state, CFG, buckets=((8, 32), (16, 64)))
    fp.warmup()
    fp.decide(_inst(5, 20, 0))  # round 0, untraced
    insts = [_inst(5, 20, 1), _inst(12, 50, 2), _inst(6, 31, 3),
             _inst(3, 9, 4)]
    trace_dir = str(tmp_path_factory.mktemp("fastpath_trace"))
    with jax.profiler.trace(trace_dir):
        list(fp.stream(insts))
        fp.decide(_inst(7, 40, 5))
    return insts + [_inst(7, 40, 5)], _host_spans(trace_dir)


def test_fastpath_spans_once_per_decision(traced_decisions):
    insts, spans = traced_decisions
    names = [e[0] for e in spans]
    for step in ("submit", "result") + SUBMIT_STEPS + RESULT_STEPS:
        assert names.count(f"corais.fastpath.{step}") == len(insts), step


@pytest.mark.parametrize("parent,steps", [("submit", SUBMIT_STEPS),
                                          ("result", RESULT_STEPS)])
def test_fastpath_steps_nest_in_their_call(traced_decisions, parent, steps):
    """Each step lies inside one call span of its thread, each call holds
    each of its steps once, in order."""
    _, spans = traced_decisions
    calls = [e for e in spans if e[0] == f"corais.fastpath.{parent}"]
    for name, s, e, thread, _ in spans:
        if name.rsplit(".", 1)[1] not in steps:
            continue
        owners = [c for c in calls if c[3] == thread and c[1] <= s
                  and e <= c[2]]
        assert len(owners) == 1, (name, s)
    for c in calls:
        inside = [x[0].rsplit(".", 1)[1] for x in spans
                  if x[3] == c[3] and c[1] <= x[1] and x[2] <= c[2]
                  and x is not c]
        assert inside == list(steps), inside


def test_fastpath_call_spans_carry_the_round(traced_decisions):
    """A decision's submit and result carry the same ``round`` (the fast
    path's counter, from 1: round 0 ran untraced), and submit its shape
    and its bucket."""
    insts, spans = traced_decisions
    sub = [e[4] for e in spans if e[0] == "corais.fastpath.submit"]
    res = [e[4] for e in spans if e[0] == "corais.fastpath.result"]
    assert [a["round"] for a in sub] == list(range(1, len(insts) + 1))
    assert sorted(a["round"] for a in res) == [a["round"] for a in sub]
    assert [a for a in res if set(a) != {"round"}] == []
    buckets = [(8, 32), (16, 64), (8, 32), (8, 32), (16, 64)]
    for a, inst, bucket in zip(sub, insts, buckets):
        assert (a["q"], a["z"]) == (inst["edge_mask"].shape[0],
                                    inst["req_mask"].shape[0])
        assert (a["q_pad"], a["z_pad"]) == bucket


def test_fastpath_transfer_moves_one_packed_leaf(traced_decisions):
    """Each transfer span says it moved one leaf, of the packed size of
    its round's bucket layout."""
    insts, spans = traced_decisions
    args = [e[4] for e in spans if e[0] == "corais.fastpath.transfer"]
    buckets = [(8, 32), (16, 64), (8, 32), (8, 32), (16, 64)]
    assert args == [
        {"leaves": 1,
         "bytes": PackedLayout.of(pad_instance(inst, *bucket)).nbytes}
        for inst, bucket in zip(insts, buckets)]


# -- kernel names -------------------------------------------------------------


def _pallas_names(jaxpr, acc):
    """The ``name`` of every pallas_call in a jaxpr, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            acc.append(eqn.params["name"])
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (list, tuple)) else [val]):
                if hasattr(sub, "jaxpr") and hasattr(sub.jaxpr, "eqns"):
                    _pallas_names(sub.jaxpr, acc)
                elif hasattr(sub, "eqns"):
                    _pallas_names(sub, acc)
    return acc


def _kernel_args():
    c, h = jnp.ones((1, 4, 8)), jnp.ones((1, 16, 8))
    w = jnp.eye(8)
    return c, h, w, w, jnp.ones((1, 4), bool)


def _fwd(c, h, wx, wy, m):
    return policy_score_fwd(c, h, wx, wy, m, interpret=True)


def _bwd(c, h, wx, wy, m):
    # the forward's own kernel is traced too; the backward's is the second
    return jax.grad(lambda *a: _fwd(*a, m).sum(), argnums=(0, 1, 2, 3))(
        c, h, wx, wy)


def _decode(c, h, wx, wy, m):
    return policy_score_decode_fwd(c, h, wx, wy, m, k=2, interpret=True)


@pytest.mark.parametrize("fn,want", [
    (_fwd, ["policy_score_fwd"]),
    (_bwd, ["policy_score_fwd", "policy_score_bwd"]),
    (_decode, ["policy_score_decode"]),
], ids=["forward", "backward", "decode"])
def test_pallas_kernels_carry_their_names(fn, want):
    """Each policy kernel has an explicit name; only the fused decode's
    holds ``policy_score_decode``, the name a trace reader matches."""
    names = _pallas_names(jax.make_jaxpr(fn)(*_kernel_args()).jaxpr, [])
    assert names == want
    assert sum("policy_score_decode" in n for n in names) == (
        fn is _decode)
