"""Roofline machinery: XLA FLOP convention calibration, HLO collective
parsing, term arithmetic."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline.analysis import CellReport
from repro.roofline.hlo_parse import collective_wire_bytes, count_ops
from repro.roofline.hw import HW


def test_xla_flop_convention_is_2mnk():
    f = jax.jit(lambda a, b: a @ b)
    low = f.lower(jax.ShapeDtypeStruct((256, 512), jnp.float32),
                  jax.ShapeDtypeStruct((512, 128), jnp.float32))
    ca = low.compile().cost_analysis()
    assert ca["flops"] == pytest.approx(2 * 256 * 512 * 128, rel=0.01)


HLO = """\
ENTRY %main {
  %p0 = f32[16,128]{1,0} parameter(0)
  %all-reduce.1 = f32[16,128]{1,0} all-reduce(%p0), replica_groups={{0,1,2,3}}, to_apply=%add
  %all-gather.2 = bf16[64,128]{1,0} all-gather(%x), replica_groups=[4,16]<=[64], dimensions={0}
  %reduce-scatter.3 = f32[4,128]{1,0} reduce-scatter(%y), replica_groups={{0,1},{2,3}}, dimensions={0}
  %cp = f32[8]{0} collective-permute(%z), source_target_pairs={{0,1}}
  %all-reduce-start.9 = f32[10]{0} all-reduce-start(%w), replica_groups={{0,1,2,3,4}}
}
"""


def test_collective_parse_counts():
    counts = count_ops(HLO)
    assert counts == {"all-reduce": 2, "all-gather": 1,
                      "reduce-scatter": 1, "collective-permute": 1}


def test_collective_wire_bytes():
    wire = collective_wire_bytes(HLO)
    # all-reduce.1: 16*128*4 = 8192 bytes, n=4 -> 2*(3/4)*8192 = 12288
    assert wire["all-reduce"] == pytest.approx(
        12288 + 10 * 4 * 2 * (4 / 5), rel=1e-6)
    # all-gather: 64*128*2 = 16384, n=16 -> *(15/16)
    assert wire["all-gather"] == pytest.approx(16384 * 15 / 16, rel=1e-6)
    # reduce-scatter: result 4*128*4=2048, n=2 -> *(n-1) = 2048
    assert wire["reduce-scatter"] == pytest.approx(2048, rel=1e-6)
    assert wire["collective-permute"] == pytest.approx(32, rel=1e-6)
    assert wire["_total"] == pytest.approx(
        sum(v for k, v in wire.items() if not k.startswith("_")), rel=1e-9)


def test_cell_report_terms():
    r = CellReport(
        arch="x", shape="train_4k", mesh="single", chips=256,
        hlo_flops_per_device=HW.peak_flops_bf16,      # exactly 1s of compute
        hlo_bytes_per_device=HW.hbm_bw / 2,           # 0.5s of memory
        wire_bytes_per_device=HW.ici_link_bw / 4,     # 0.25s of collective
        collective_ops={}, collective_breakdown={},
        temp_bytes_per_device=0, arg_bytes_per_device=0, out_bytes_per_device=0,
        model_flops=HW.peak_flops_bf16 * 256 * 0.8,
        params_total=1e9, params_active=1e9, compile_seconds=1.0)
    t = r.terms()
    assert t["compute_s"] == pytest.approx(1.0)
    assert t["memory_s"] == pytest.approx(0.5)
    assert t["collective_s"] == pytest.approx(0.25)
    assert t["dominant"] == "compute"
    assert t["useful_flop_ratio"] == pytest.approx(0.8)
    assert t["roofline_fraction"] == pytest.approx(1.0)
