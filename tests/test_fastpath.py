"""Online serving fast path: bucket padding, double-buffered decision loop,
SLO evaluation, and the drift-check schema compatibility."""
import importlib.util
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import InstanceConfig, generate_instance
from repro.core.inference import policy_decide
from repro.core.policy import PolicyConfig, corais_init
from repro.serving.fastpath import (DEFAULT_BUCKETS, DecisionFastPath,
                                    PackedLayout, SLOSpec, evaluate_slo,
                                    pad_instance)

CFG = PolicyConfig(d_model=32, ff_hidden=64, edge_layers=2, request_layers=1)


def _inst(q, z, seed=0):
    return {k: np.asarray(v) for k, v in generate_instance(
        np.random.default_rng(seed),
        InstanceConfig(num_edges=q, num_requests=z)).items()}


@pytest.fixture(scope="module")
def policy():
    return corais_init(jax.random.PRNGKey(0), CFG)


# -- padding + buckets -------------------------------------------------------


def test_pad_instance_is_mask_preserving():
    inst = _inst(4, 6)
    padded = pad_instance(inst, 7, 11)
    assert padded["edge_mask"].shape == (7,)
    assert padded["req_mask"].shape == (11,)
    assert padded["w"].shape == (7, 7)
    np.testing.assert_array_equal(padded["edge_mask"][:4],
                                  inst["edge_mask"])
    assert not padded["edge_mask"][4:].any()
    assert not padded["req_mask"][6:].any()
    np.testing.assert_array_equal(padded["req_size"][:6], inst["req_size"])
    with pytest.raises(ValueError, match="exceeds pad"):
        pad_instance(inst, 3, 11)


def test_bucket_selection(policy):
    params, state = policy
    fp = DecisionFastPath(params, state, CFG,
                          buckets=((8, 32), (16, 64), (4, 128)))
    assert fp.bucket_for(3, 10) == (4, 128)  # sorted: smallest that fits
    assert fp.bucket_for(5, 10) == (8, 32)
    assert fp.bucket_for(9, 60) == (16, 64)
    with pytest.raises(ValueError, match="exceeds every fast-path bucket"):
        fp.bucket_for(17, 10)


# -- decision loop -----------------------------------------------------------


def test_fastpath_matches_policy_decide(policy):
    """Bucket padding + staging + fused decode must reproduce the plain
    policy_decide decision on the unpadded instance (mask invariance),
    across buckets."""
    params, state = policy
    fp = DecisionFastPath(params, state, CFG, buckets=((8, 32), (16, 64)))
    for q, z, seed in ((5, 20, 0), (8, 30, 1), (12, 50, 2)):
        inst = _inst(q, z, seed)
        got = fp.decide(inst)
        want = np.asarray(policy_decide(
            None, params, state, jax.tree.map(jnp.asarray, inst), CFG))
        assert got.shape == (z,) and got.dtype == np.int32
        np.testing.assert_array_equal(got, want, err_msg=f"q={q} z={z}")


def test_fastpath_stream_matches_sync(policy):
    """The pipelined (double-buffered) stream yields exactly the sync
    decisions, in order — staging round n+1 never corrupts round n."""
    params, state = policy
    insts = [_inst(5, 20, s) for s in range(6)]
    fp_sync = DecisionFastPath(params, state, CFG, buckets=((8, 32),))
    fp_stream = DecisionFastPath(params, state, CFG, buckets=((8, 32),))
    sync = [fp_sync.decide(i) for i in insts]
    streamed = list(fp_stream.stream(insts))
    assert len(streamed) == len(sync)
    for a, b in zip(sync, streamed):
        np.testing.assert_array_equal(a, b)


def test_fastpath_warmup_compiles_buckets(policy):
    params, state = policy
    fp = DecisionFastPath(params, state, CFG, buckets=((8, 32), (16, 64)))
    compile_ms = fp.warmup()
    assert set(compile_ms) == {(8, 32), (16, 64)}
    assert all(ms > 0 for ms in compile_ms.values())
    # warmed executables answer without recompiling (latency way under
    # compile time)
    t0 = time.perf_counter()
    fp.decide(_inst(5, 20))
    assert (time.perf_counter() - t0) * 1e3 < compile_ms[(8, 32)]


def test_fastpath_modes_and_donation_default(policy):
    params, state = policy
    # greedy default resolves normalize off; sample keeps true log-probs
    fp_g = DecisionFastPath(params, state, CFG, buckets=((8, 32),))
    assert fp_g.spec.normalize is False
    fp_s = DecisionFastPath(params, state, CFG, buckets=((8, 32),),
                            mode="sample", num_samples=8)
    assert fp_s.spec.normalize is True
    a = fp_s.decide(_inst(5, 20, 3))
    assert a.shape == (20,) and a.max() < 5
    # CPU resolves donate off automatically (jax can't donate on cpu)
    if jax.default_backend() == "cpu":
        assert fp_g.donate is False


# -- packed staging ----------------------------------------------------------

TEST_BUCKETS = ((8, 32), (16, 64))
# float32 bit patterns a float path may corrupt: the two smallest
# subnormals, a negative subnormal, -0.0, both infinities, the default NaN
# and a NaN carrying a payload
HOSTILE_F32 = np.array([0x00000001, 0x007FFFFF, 0x80000010, 0x80000000,
                        0x7F800000, 0xFF800000, 0x7FC00000, 0x7FA12345],
                       np.uint32).view(np.float32)


def _hostile(inst):
    """``inst`` with HOSTILE_F32 in its float leaves and the int32 limits
    among its request sources."""
    inst = {k: np.array(v, copy=True) for k, v in inst.items()}
    n = len(HOSTILE_F32)
    inst["req_size"][:n] = HOSTILE_F32
    inst["workload"].reshape(-1)[:n] = HOSTILE_F32
    inst["w"][0, :2] = HOSTILE_F32[2:4]
    inst["ct"] = HOSTILE_F32[-1]
    inst["req_src"][:2] = (np.iinfo(np.int32).min, np.iinfo(np.int32).max)
    return inst


def _assert_unpacks_to_padded(layout, buf, inst, bucket):
    """The device unpack of ``buf`` is pad_instance(inst), leaf for leaf:
    same dtype, shape and bits."""
    got = jax.jit(layout.unpack)(jnp.asarray(buf))
    want = pad_instance(inst, *bucket)
    assert sorted(got) == sorted(want)
    for k, w in want.items():
        g, w = np.asarray(got[k]), np.asarray(w)
        assert (g.dtype, g.shape) == (w.dtype, w.shape), k
        np.testing.assert_array_equal(g.reshape(-1).view(np.uint8),
                                      w.reshape(-1).view(np.uint8), err_msg=k)


@pytest.mark.parametrize("bucket,extra", [(b, False) for b in
                                          DEFAULT_BUCKETS + TEST_BUCKETS]
                         + [((16, 64), True)])
def test_packed_stage_is_bit_exact(policy, bucket, extra):
    """Staging into the packed buffer and unpacking it on the device gives
    exactly pad_instance's leaves; extra (schema-v3) keys travel too, in a
    layout and program of their own."""
    params, state = policy
    fp = DecisionFastPath(params, state, CFG, buckets=(bucket,))
    q, z = bucket[0] - 3, bucket[1] * 3 // 5
    inst = _hostile(_inst(q, z, 7))
    if extra:
        inst.update(tier=np.linspace(0, 1, q, dtype=np.float32),
                    req_priority=np.arange(z, dtype=np.int32),
                    req_cached=np.arange(z) % 2 == 0)
    lane, buf = fp._stage(inst, bucket)
    assert buf.dtype == np.int32 and buf.ndim == 1
    assert buf.nbytes == lane.layout.nbytes
    assert all(off % 128 == 0 for off in lane.layout.offsets)
    _assert_unpacks_to_padded(lane.layout, buf, inst, bucket)
    if extra:
        plain, _ = fp._stage(_inst(q, z, 8), bucket)
        assert plain is not lane and plain.fn is not lane.fn
        assert {"tier", "req_priority", "req_cached"} == (
            {k for k, _, _ in lane.layout.leaves}
            - {k for k, _, _ in plain.layout.leaves})


def test_packed_stage_clears_stale_padding(policy):
    """A small instance staged into the ping-pong buffer a larger one used
    two rounds before decides as policy_decide does unpadded: nothing of
    the larger round is left in the padding."""
    params, state = policy
    bucket = (16, 64)
    fp = DecisionFastPath(params, state, CFG, buckets=(bucket,))
    big, other, small = _inst(15, 60, 1), _inst(10, 40, 2), _inst(4, 9, 3)
    fp.decide(big)    # slot 0
    fp.decide(other)  # slot 1
    got = fp.decide(small)  # slot 0 again
    want = np.asarray(policy_decide(
        None, params, state, jax.tree.map(jnp.asarray, small), CFG))
    np.testing.assert_array_equal(got, want)
    lane, = fp._lanes.values()
    assert lane.slot == 1  # the small round went to slot 0
    _assert_unpacks_to_padded(lane.layout, lane.bufs[0], small, bucket)


def test_fastpath_transfers_one_of_two_buffers_per_bucket(policy,
                                                          monkeypatch):
    """Every round moves one int32 array to the device, and per bucket it
    is one of the same two host arrays, in turn: no staging allocation per
    round."""
    params, state = policy
    fp = DecisionFastPath(params, state, CFG, buckets=TEST_BUCKETS)
    fp.warmup()
    sent = []
    put = jax.device_put
    monkeypatch.setattr(jax, "device_put",
                        lambda x, *a, **k: sent.append(x) or put(x, *a, **k))
    insts = [_inst(5, 20, 0), _inst(12, 50, 1)] * 3 + [_inst(6, 25, 2)]
    list(fp.stream(insts))
    assert len(sent) == len(insts)
    assert all(isinstance(x, np.ndarray) and x.dtype == np.int32
               and x.ndim == 1 for x in sent)
    lanes = {sig[0]: lane for sig, lane in fp._lanes.items()}
    assert sorted(lanes) == sorted(TEST_BUCKETS)
    small = [x for x, i in zip(sent, insts) if i["req_mask"].shape[0] < 32]
    large = [x for x, i in zip(sent, insts) if i["req_mask"].shape[0] >= 32]
    for bucket, xs in (((8, 32), small), ((16, 64), large)):
        bufs = lanes[bucket].bufs
        # warmup staged slot 0, so rounds start at slot 1
        assert [x is bufs[(i + 1) % 2] for i, x in enumerate(xs)] == (
            [True] * len(xs)), bucket


# -- SLO ---------------------------------------------------------------------


def test_slo_spec_check():
    slo = SLOSpec(p50_ms=1.0, p95_ms=2.0, p99_ms=3.0, name="x")
    rep = slo.check([0.5] * 90 + [5.0] * 10)
    assert rep["p50_ok"] and not rep["p95_ok"] and not rep["p99_ok"]
    assert rep["pass"] is False
    assert rep["samples"] == 100
    ok = slo.check([0.5, 0.6])
    assert ok["pass"] is True
    with pytest.raises(ValueError, match="no latency samples"):
        slo.check([])


def test_evaluate_slo_report_structure(policy):
    params, state = policy
    fp = DecisionFastPath(params, state, CFG, buckets=((8, 32),))
    insts = [_inst(5, 20, s) for s in range(3)]
    rep = evaluate_slo(fp, insts, SLOSpec(1e4, 1e4, 1e4, name="test-path"))
    assert rep["pass"] is True and rep["name"] == "test-path"
    assert rep["samples"] == 3  # warmup rounds not counted
    assert rep["buckets"] == [[8, 32]]
    assert "8x32" in rep["compile_ms"]
    for p in (50, 95, 99):
        assert rep[f"p{p}_ms"] > 0 and rep[f"p{p}_slo_ms"] == 1e4


def test_evaluate_slo_warms_cold_buckets_after_partial_warmup(policy):
    """A partial warmup must not suppress warming the buckets the workload
    actually hits: previously any non-empty compile_ms skipped warmup
    entirely, so the first decision in a cold bucket paid jit compilation
    inside a measured SLO sample."""
    params, state = policy
    fp = DecisionFastPath(params, state, CFG, buckets=((8, 32), (16, 64)))
    fp.warmup([(8, 32)])  # partial: the workload's bucket stays cold
    insts = [_inst(12, 50, s) for s in range(3)]  # all land in (16, 64)
    rep = evaluate_slo(fp, insts, SLOSpec(1e4, 1e4, 1e4))
    # the hit bucket was compiled before measurement started...
    assert (16, 64) in fp.compile_ms
    # ...only the workload decisions were measured...
    assert rep["samples"] == len(insts)
    # ...and no measured sample contains the (16, 64) compile
    assert rep["p95_ms"] < fp.compile_ms[(16, 64)]


# -- drift-check schema compatibility ----------------------------------------


def _load_drift_module():
    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "check_latency_drift.py")
    spec = importlib.util.spec_from_file_location("check_latency_drift", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _v1_cell(backend, q, z, p95):
    return {"backend": backend, "num_edges": q, "num_requests": z,
            "single": {"p95_ms": p95}}


def _v2_cell(backend, q, z, stage, decode, p95):
    c = _v1_cell(backend, q, z, p95)
    c.update(stage=stage, decode=decode)
    return c


def test_drift_check_reads_v1_and_v2(tmp_path):
    """The drift gate keys v1 cells as (…, 'decision', 'host'), so v1 and
    v2 reports/baselines interoperate and fused cells gate separately."""
    drift = _load_drift_module()
    v1 = {"schema": "corais.policy_latency.v1",
          "cells": [_v1_cell("pallas", 5, 20, 1.0)]}
    v2 = {"schema": "corais.policy_latency.v2",
          "cells": [_v2_cell("pallas", 5, 20, "decision", "host", 1.1),
                    _v2_cell("pallas", 5, 20, "decision", "fused", 0.4),
                    _v2_cell("pallas", 5, 20, "head", "fused", 0.1)]}
    p1, p2 = tmp_path / "v1.json", tmp_path / "v2.json"
    p1.write_text(json.dumps(v1))
    p2.write_text(json.dumps(v2))
    k1 = drift.load_report_cells(str(p1))
    k2 = drift.load_report_cells(str(p2))
    assert ("pallas", 5, 20, "decision", "host") in k1
    assert set(k1) < set(k2)

    # v2 report vs v1-schema baseline: overlapping host cell gates, fused
    # cells are new and skipped
    base = {"schema": "corais.policy_latency_baseline.v1",
            "cells": [{"backend": "pallas", "num_edges": 5,
                       "num_requests": 20, "p95_ms": 1.0}]}
    bp = tmp_path / "base.json"
    bp.write_text(json.dumps(base))
    assert drift.check(str(p2), str(bp), factor=4.0, floor_ms=0.0) == 0
    # and the gate still trips on real drift
    slow = {"schema": "corais.policy_latency.v2",
            "cells": [_v2_cell("pallas", 5, 20, "decision", "host", 99.0)]}
    ps = tmp_path / "slow.json"
    ps.write_text(json.dumps(slow))
    assert drift.check(str(ps), str(bp), factor=4.0, floor_ms=0.0) == 1


def test_drift_write_baseline_roundtrip(tmp_path):
    """write_baseline distills a v2 report into a v2 baseline whose cells
    gate that same report cleanly (including fused/head cells)."""
    drift = _load_drift_module()
    report = {"schema": "corais.policy_latency.v2",
              "cells": [_v2_cell("pallas", 5, 20, "decision", "fused", 0.4),
                        _v2_cell("pallas", 100, 1000, "head", "host", 2.2),
                        _v2_cell("xla", 5, 20, "decision", "host", 0.9)]}
    rp, bp = tmp_path / "r.json", tmp_path / "b.json"
    rp.write_text(json.dumps(report))
    drift.write_baseline(str(rp), str(bp))
    payload = json.loads(bp.read_text())
    assert payload["schema"] == "corais.policy_latency_baseline.v2"
    assert len(payload["cells"]) == 3
    assert {c["stage"] for c in payload["cells"]} == {"decision", "head"}
    assert drift.check(str(rp), str(bp), factor=4.0, floor_ms=0.0) == 0


def test_drift_check_fails_on_missing_baseline_cells(tmp_path, capsys):
    """Baseline cells absent from the fresh report fail the gate by default
    (a dropped grid point or renamed backend must not pass silently) and
    are listed; --allow-missing opts out for intentional grid shrinks."""
    drift = _load_drift_module()
    report = {"schema": "corais.policy_latency.v2",
              "cells": [_v2_cell("pallas", 5, 20, "decision", "host", 1.0)]}
    base = {"schema": "corais.policy_latency_baseline.v2",
            "cells": [{"backend": "pallas", "num_edges": 5,
                       "num_requests": 20, "stage": "decision",
                       "decode": "host", "p95_ms": 1.0},
                      {"backend": "xla", "num_edges": 100,
                       "num_requests": 1000, "stage": "decision",
                       "decode": "host", "p95_ms": 2.0}]}
    rp, bp = tmp_path / "r.json", tmp_path / "b.json"
    rp.write_text(json.dumps(report))
    bp.write_text(json.dumps(base))
    assert drift.check(str(rp), str(bp), factor=4.0, floor_ms=0.0) == 1
    out = capsys.readouterr().out
    assert "MISSING" in out and "xla" in out
    assert drift.check(str(rp), str(bp), factor=4.0, floor_ms=0.0,
                       allow_missing=True) == 0
    # a regression in a common cell still fails even with allow_missing
    slow = {"schema": "corais.policy_latency.v2",
            "cells": [_v2_cell("pallas", 5, 20, "decision", "host", 99.0)]}
    sp = tmp_path / "s.json"
    sp.write_text(json.dumps(slow))
    assert drift.check(str(sp), str(bp), factor=4.0, floor_ms=0.0,
                       allow_missing=True) == 1
