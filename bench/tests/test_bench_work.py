"""Work counts and the peak table of the benchmark (bench/benchlib)."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402

from benchlib import gen, peaks, weights, work  # noqa: E402

LAW = {"phi_low": 0.0, "phi_high": 1.0, "replicas_high": 4, "backlog_high": 100,
       "ct": 1.0}


def _pol(d):
    return {"d_model": d, "num_heads": 4, "edge_layers": 5, "request_layers": 3,
            "ff_hidden": 2 * d, "edge_features": 8, "req_features": 3,
            "tanh_clip": 10.0, "feature_scale": 0.1}


def _flops(fn, *args):
    ca = jax.jit(fn).lower(*args).compile().cost_analysis()
    return (ca[0] if isinstance(ca, list) else ca)["flops"]


@pytest.mark.parametrize("q,z,d", [(10, 100, 64), (25, 250, 128)])
def test_encode_flops_match_xla(q, z, d):
    """The analytic count is XLA's for the same real-shaped call, less the
    elementwise work (norms, softmax, relu) it leaves out by design: at
    these small widths that is at most 12% of XLA's count (1% at d=256)."""
    from repro.core.policy import corais_encode

    pol = _pol(d)
    params, state = weights.make_policy(0, pol)
    cfg = weights.program_config(pol, "xla")
    inst = gen.snapshot(gen.rng_for(0), q, z, LAW)
    xla = _flops(lambda p, s, i: corais_encode(p, s, i, cfg)[:2],
                 params, state, inst)
    ours = work.encode_flops(q, z, pol)
    assert 0.88 * xla <= ours <= xla


@pytest.mark.parametrize("q,z,d", [(10, 100, 256), (100, 1000, 64)])
def test_decode_kernel_flops_match_xla(q, z, d):
    """The fused decode's three products, in the kernel's association, are
    exactly XLA's matmul count."""
    def dec(c, h, wx, wy):
        return h @ (wy @ (c @ wx).T)

    xla = _flops(dec, jnp.ones((q, d)), jnp.ones((z, d)), jnp.ones((d, d)),
                 jnp.ones((d, d)))
    assert work.decode_kernel_work([(q, z)], d)[0] == xla


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peak table entry"):
        peaks.peak("TPU v99 imaginary")
    assert peaks.peak("TPU v5 lite")["flops_per_s"] == 197e12


def test_roofline_counts_real_shapes_not_padding():
    """Work is a function of the real (q, z): a bucket-padded call of the
    same round counts the same."""
    assert work.decode_kernel_work([(10, 60)], 256) != work.decode_kernel_work(
        [(10, 100)], 256)
    assert work.decision_flops(7, 60, _pol(256)) < work.decision_flops(
        10, 100, _pol(256))


def test_decode_call_reads_the_weights_once():
    """A batched round is one kernel call whose (d, d) weights are one block
    for the whole grid: 256 instances in one call read them once, 256
    calls of one instance 256 times, and the FLOPs are the same."""
    d, inst = 256, [(10, 51 + i % 50) for i in range(256)]
    f1, b1 = work.decode_kernel_work(inst, d)
    fs = [work.decode_kernel_work([x], d) for x in inst]
    assert f1 == sum(f for f, _ in fs)
    assert sum(b for _, b in fs) - b1 == 255 * 2 * d * d * work.F32
    q, z = inst[0]
    assert work.decode_kernel_work([], d)[1] == 2 * d * d * work.F32
    assert work.decode_kernel_work([(q, z)], d)[1] == work.F32 * (
        2 * d * d + q * d + z * d + q + 2 * z)
