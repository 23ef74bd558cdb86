import os

if "XLA_FLAGS" not in os.environ:
    # set BEFORE jax init; overridden by --devices via re-exec below
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

"""Elastic-scaling demonstration: train -> checkpoint -> resume on a
DIFFERENT mesh size (node failure / pod resize), with bitwise-identical
parameters after resharding.

    python -m repro.launch.elastic --steps 8

Phase A trains a reduced LM on a (4, 2) mesh and checkpoints. Phase B
re-creates the world with HALF the devices (simulating a failed pod),
builds a (2, 2) mesh, restores the same checkpoint with the new shardings,
and continues training. The checkpoint layer stores host-gathered arrays
with logical paths, so any mesh that fits the divisibility rules works.
"""
import argparse
import subprocess
import sys

import jax
import jax.numpy as jnp

from repro.checkpoint import Checkpointer
from repro.configs import get_reduced_config
from repro.data.synthetic import SyntheticTokens
from repro.launch.mesh import auto_mesh
from repro.models import lm
from repro.optim import AdamConfig, adam_init, adam_update
from repro.sharding import specs as S


def run_phase(phase: str, mesh_shape, steps: int, ckpt_dir: str, arch: str):
    cfg = get_reduced_config(arch)
    mesh = auto_mesh(mesh_shape, ("data", "model"))
    adam = AdamConfig(lr=1e-3)
    key = jax.random.PRNGKey(0)
    params_shapes = jax.eval_shape(lambda: lm.init_params(key, cfg))
    pspecs = S.param_specs(params_shapes, cfg, mesh)
    opt_shapes = jax.eval_shape(lambda: adam_init(params_shapes, adam))
    ospecs = S.opt_state_specs(opt_shapes, pspecs, cfg, mesh)

    ckpt = Checkpointer(ckpt_dir, every=1, async_save=False)
    pipe = SyntheticTokens(cfg.vocab_size, batch=8, seq=32)
    restored = ckpt.restore_latest(
        {"params": params_shapes, "opt_state": opt_shapes},
        shardings={"params": pspecs, "opt_state": ospecs})
    if restored is None:
        params = jax.jit(lambda k: lm.init_params(k, cfg),
                         out_shardings=pspecs)(key)
        opt_state = jax.jit(lambda p: adam_init(p, adam),
                            out_shardings=ospecs)(params)
        start = 0
    else:
        params = restored["tree"]["params"]
        opt_state = restored["tree"]["opt_state"]
        pipe.load_state_dict(restored["extras"]["pipeline"])
        start = restored["step"]
        print(f"[{phase}] restored step {start} onto mesh {mesh_shape} "
              f"({len(jax.devices())} devices)")

    @jax.jit
    def step(params, opt_state, batch):
        (loss, _), grads = jax.value_and_grad(
            lambda p: lm.train_loss(p, batch, cfg, 1), has_aux=True)(params)
        params, opt_state = adam_update(params, grads, opt_state, adam)
        return params, opt_state, loss

    with mesh:
        for i in range(start, start + steps):
            batch = jax.tree.map(jnp.asarray, next(pipe))
            params, opt_state, loss = step(params, opt_state, batch)
            print(f"[{phase}] step {i} mesh={mesh_shape} loss={float(loss):.4f}")
    ckpt.save(start + steps, {"params": params, "opt_state": opt_state},
              extras={"pipeline": pipe.state_dict()})
    ckpt.wait()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--ckpt", default="/tmp/repro_elastic_ckpt")
    ap.add_argument("--phase", default=None, help="internal")
    ap.add_argument("--devices", type=int, default=None, help="internal")
    args = ap.parse_args()

    if args.phase == "A":
        run_phase("A", (4, 2), args.steps, args.ckpt, args.arch)
        return
    if args.phase == "B":
        run_phase("B", (2, 2), args.steps, args.ckpt, args.arch)
        return

    # orchestrate: phase A on 8 devices, phase B on 4 (simulated pod loss)
    import shutil
    shutil.rmtree(args.ckpt, ignore_errors=True)
    for phase, devs in (("A", 8), ("B", 4)):
        env = dict(os.environ)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devs}"
        cmd = [sys.executable, "-m", "repro.launch.elastic", "--phase", phase,
               "--steps", str(args.steps), "--ckpt", args.ckpt,
               "--arch", args.arch]
        print(f"== phase {phase}: {devs} devices ==")
        subprocess.run(cmd, check=True, env=env)
    print("elastic restart OK: trained, shrank the mesh 8->4, resumed.")


if __name__ == "__main__":
    main()
