"""The policy kernels compile for a TPU v5e at the paper's width.

Interpret mode (every other kernel test) cannot see what the TPU's compiler
refuses: block shapes off the (8, 128) tiling, too much fast memory. These
tests compile ``kernels/policy_score.py`` forward, custom-VJP backward and
fused decode with ``interpret=False`` against a described ``v5e:2x2``
topology (no chip needed) and check that the Pallas kernel is in each
executable. The topology is described inside a fixture, never at import:
only one process at a time may load the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.policy_score import (policy_score_decode_fwd,
                                        policy_score_fwd)

D = 256  # PolicyConfig().d_model: the paper's width


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _args(sharding, b, q, z):
    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return (spec((b, q, D)), spec((b, z, D)), spec((D, D)), spec((D, D)),
            spec((b, q), jnp.bool_))


def _fwd(c, h, wx, wy, m):
    return policy_score_fwd(c, h, wx, wy, m, interpret=False)


def _grad(c, h, wx, wy, m):
    return jax.grad(lambda *a: _fwd(*a, m).sum(), argnums=(0, 1, 2, 3))(
        c, h, wx, wy)


def _decode(c, h, wx, wy, m):
    return policy_score_decode_fwd(c, h, wx, wy, m, k=3, interpret=False)


@pytest.mark.parametrize("fn", [_fwd, _grad, _decode],
                         ids=["forward", "grad", "decode"])
@pytest.mark.parametrize("b,q,z", [(1, 10, 100), (1, 100, 1000), (8, 10, 100)])
def test_policy_score_compiles_for_v5e(one_chip, fn, b, q, z):
    compiled = jax.jit(fn).lower(*_args(one_chip, b, q, z)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _vmap_decode(c, h, wx, wy, m):
    # as the batched rollout calls it: per instance, under vmap
    return jax.vmap(lambda c, h, m: policy_score_decode_fwd(
        c, h, wx, wy, m, interpret=False))(c, h, m)


@pytest.mark.parametrize("fn,names", [
    (_fwd, ["policy_score_fwd"]),
    (_grad, ["policy_score_bwd", "policy_score_fwd"]),
    (_decode, ["policy_score_decode"]),
    (_vmap_decode, ["policy_score_decode"]),
], ids=["forward", "grad", "decode", "vmap-decode"])
def test_policy_kernels_name_their_tpu_ops(one_chip, fn, names):
    """A TPU trace names a Pallas kernel's operation after its HLO
    instruction, which holds the kernel's explicit ``name`` whatever
    encloses it (under ``grad`` or ``vmap``, wrapped as ``jvp_...`` or
    ``vmap_...``): the name the benchmark's roofline readers match."""
    text = jax.jit(fn).lower(*_args(one_chip, 2, 10, 100)).compile().as_text()
    got = re.findall(r"%(\S+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
                     text)
    assert len(got) == len(names), got
    for name in names:
        assert sum(name in g for g in got) == 1, got
