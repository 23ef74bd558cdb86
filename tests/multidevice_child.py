"""Child process for tests/test_multidevice.py — needs 8 host devices,
which must be forced before jax initializes (hence the subprocess)."""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import get_reduced_config
from repro.configs.base import ShapeConfig
from repro.data.synthetic import SyntheticTokens
from repro.launch.mesh import auto_mesh
from repro.launch.steps import TrainKnobs, build_train_step
from repro.models import lm
from repro.optim import AdamConfig, adam_init, adam_update, clip_by_global_norm
from repro.optim.grad_utils import compressed_psum


def check_compressed_psum():
    mesh = auto_mesh((8,), ("data",))
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 64)) * 3.0

    def body(xs):
        return compressed_psum(xs, "data", 8)

    out = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("data", None),
                                out_specs=P("data", None)))(x)
    expected = jnp.broadcast_to(jnp.sum(x, axis=0, keepdims=True), x.shape)
    err = float(jnp.abs(out - expected).max())
    # int8 absmax quantization: per-element error <= shards * scale/2
    scale = float(jnp.max(jnp.abs(x)) / 127.0)
    assert err <= 8 * scale / 2 + 1e-6, (err, scale)
    print("compressed_psum ok", err)


def check_sharded_train_equivalence():
    cfg = get_reduced_config("olmo-1b")
    shape = ShapeConfig("tiny_train", seq_len=32, global_batch=8, kind="train")
    mesh = auto_mesh((4, 2), ("data", "model"))
    knobs = TrainKnobs(lr=1e-2, donate=False)
    step, _, _ = build_train_step(cfg, mesh, shape, knobs)

    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    adam = AdamConfig(lr=knobs.lr)
    opt = adam_init(params, adam)
    pipe = SyntheticTokens(cfg.vocab_size, 8, 32, seed=3)
    batch = jax.tree.map(jnp.asarray, next(pipe))

    with mesh:
        p1, o1, metrics = step(params, opt, batch)
    sharded_loss = float(metrics["loss_total"])

    # plain single-device reference step
    def ref_step(params, opt_state, batch):
        (loss, _), grads = jax.value_and_grad(
            lambda p: lm.train_loss(p, batch, cfg, 1), has_aux=True)(params)
        grads, _ = clip_by_global_norm(grads, knobs.grad_clip)
        params, opt_state = adam_update(params, grads, opt_state, adam)
        return params, opt_state, loss

    p2, o2, ref_loss = jax.jit(ref_step)(params, opt, batch)
    assert abs(sharded_loss - float(ref_loss)) < 1e-3, (sharded_loss, float(ref_loss))
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-4)
    print("sharded==single train step ok", sharded_loss)


if __name__ == "__main__":
    check_compressed_psum()
    check_sharded_train_equivalence()
    print("MULTIDEVICE_OK")
