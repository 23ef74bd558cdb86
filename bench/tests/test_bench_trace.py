"""The reduction from a profiler trace to the benchmark's numbers
(bench/benchlib/trace.py), on a small trace recorded on one TPU v5e chip
(a quarter-second serve-paper-10e-poisson window) and on synthetic
intervals."""
import gzip
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import pytest  # noqa: E402

from benchlib import readers, work  # noqa: E402
from benchlib import trace as tr  # noqa: E402

DATA = Path(__file__).resolve().parent / "data" / "serve_paper_small.xplane.pb.gz"


@pytest.fixture(scope="module")
def chip_trace(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(gzip.decompress(DATA.read_bytes()))
    return tr.load(str(path))


def test_union_merges_and_clips():
    assert tr.union([(5, 8), (0, 2), (1, 3), (7, 12)], 1, 10) == [(1, 3), (5, 10)]
    assert tr.union([(0, 1)], 2, 3) == []


def test_segments_charge_the_innermost_span():
    spans = [tr.Span("a", 0, 10), tr.Span("b", 2, 4), tr.Span("c", 10, 12)]
    assert tr._segments(spans) == [(0, 2, "a"), (2, 4, "b"), (4, 10, "a"),
                                   (10, 12, "c")]


def test_idle_gaps_split_over_host_spans():
    t = tr.Trace(ops=[tr.Op("k", 2, 5, "k", "d0"), tr.Op("k", 8, 9, "k", "d0")],
                 spans=[tr.Span("bench.window", 0, 10),
                        tr.Span("bench.wait", 0, 3), tr.Span("bench.result", 5, 9)],
                 devices=["d0"])
    assert tr.busy_s(t, 0, 10) == pytest.approx(4e-9)
    gaps = dict(tr.idle_gaps(t, 0, 10))
    assert gaps == pytest.approx({"bench.wait": 2e-9, "bench.result": 3e-9,
                                  "no span": 1e-9})


def test_chip_trace_has_device_ops_and_host_spans(chip_trace):
    assert chip_trace.devices == ["/device:TPU:0"]
    names = {s.name for s in chip_trace.spans}
    assert {"bench.window", "bench.submit", "bench.result"} <= names
    lo, hi = chip_trace.window()
    assert hi > lo and tr.ops_in(chip_trace, lo, hi)


def test_chip_trace_kernel_runs_once_per_decision(chip_trace):
    lo, hi = chip_trace.window()
    submits = [s for s in chip_trace.spans if s.name == "bench.submit"]
    ops = tr.ops_in(chip_trace, lo, hi)
    secs, n = tr.kernel_time(ops, "policy_score_decode")
    assert n == len(submits) > 0 and secs > 0


def test_chip_trace_roofline_reads_the_named_kernel_alone(chip_trace):
    """The share is read from the kernel's own events, with one call of
    work counted for each; under another name, or with the calls
    miscounted, the metric goes silent instead of reading other work."""
    lo, hi = chip_trace.window()
    n = tr.kernel_time(tr.ops_in(chip_trace, lo, hi), "policy_score_decode")[1]

    def share(kernel, calls):
        data = {"trace": chip_trace, "lo": lo, "hi": hi, "pol": {"d_model": 256},
                "device_kind": "TPU v5 lite", "kernel_calls": {kernel: calls}}
        return readers.kernel_roofline(data, kernel, work.decode_kernel_work)

    # every round of the trace at its bucket's largest size: an upper end
    assert 0 < share("policy_score_decode", [[(10, 100)]] * n) <= 100
    assert share("policy_score_decode", [[(10, 100)]] * (n + 1)) is None
    assert share("policy_score_fwd", [[(10, 100)]] * n) is None


def test_chip_trace_busy_and_idle_add_up(chip_trace):
    lo, hi = chip_trace.window()
    busy = tr.busy_s(chip_trace, lo, hi)
    idle = sum(s for _, s in tr.idle_gaps(chip_trace, lo, hi, n=100))
    assert 0 < busy < (hi - lo) * tr.NS
    assert busy + idle == pytest.approx((hi - lo) * tr.NS, rel=1e-9)
    top = tr.top_ops(tr.ops_in(chip_trace, lo, hi))
    assert 0 < len(top) <= 10
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)
