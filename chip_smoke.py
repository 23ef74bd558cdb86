#!/usr/bin/env python3
"""Drive the scheduler's main path once on a TPU and check what comes out.

    python chip_smoke.py [--seed N]       one chip: decision server, engine,
                                          trainer
    python chip_smoke.py --chips 4        four chips: fleet-sharded rollout
                                          and data-parallel training epoch,
                                          each against its one-device run

The policy is the paper's full width (``PolicyConfig()``: d_model 256,
L=5 edge layers, K=3 request layers, 8 heads, FC 512, ~4M parameters),
initialised from ``--seed``. Each phase prints one line; the last line is
``{"ok": true, "device": {...}}``. Without a TPU, or if any phase fails,
the script exits non-zero and prints no result line. Everything runs in
this one process (a chip belongs to one process at a time).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

#: Decision check against the ``ref`` backend, in eq-16 score units (the
#: clipped compatibility C*tanh(u), C=10; within one request, log-prob
#: differences equal score differences). The served paths run the encoder
#: and head at the TPU's default f32 matmul precision (one bf16 pass), the
#: reference at full f32 precision, so near-ties may resolve differently:
#: a decision passes when the reference score of the chosen edge is within
#: this much of the reference maximum for that request.
REGRET_TOL = 0.05
#: Engine vs event-driven oracle: the tolerance of tests/test_engine.py.
FINISH_RTOL, FINISH_ATOL = 1e-5, 1e-4
#: Sharded vs one-device summaries: the tolerance of tests/fleet_child.py.
FLEET_RTOL = 1e-5

PAPER_EDGES, ROUNDS, DT = 10, 12, 0.25
#: ~100 arrivals per 0.25-s round: the paper's 10-edge x 100-request scale.
PAPER_RATE = 400.0
ENGINE_BATCH = 64
#: Oracle replays of this many engine batch elements (the event-driven
#: simulator is host Python).
ORACLE_ELEMENTS = 2
#: Per bucket: (edges, requests) of the seeded instances answered there —
#: the bucket's own size and one that needs padding.
BUCKET_SIZES = {
    (10, 100): ((10, 100), (7, 60)),
    (25, 250): ((20, 200), (25, 250)),
    (50, 500): ((50, 500), (40, 300)),
    (100, 1000): ((100, 1000), (80, 900)),
}
ROUNDS_PER_SIZE = 2


class PhaseError(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise PhaseError(msg)


def emit(phase: str, **fields):
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{phase}] {body}", flush=True)


def compiled_with_kernel(jitted, *args, what: str):
    """Compile ``jitted`` for ``args`` and check that the Pallas kernel is
    in the executable (``tpu_custom_call``), not interpreted or dropped."""
    compiled = jitted.lower(*args).compile()
    check("tpu_custom_call" in compiled.as_text(),
          f"{what}: no tpu_custom_call in the compiled HLO")
    return compiled


# -- phase 1: decision server ------------------------------------------------


def phase_decision_server(params, pstate, pcfg, seed):
    import jax
    import numpy as np

    from repro.core.instances import InstanceConfig, generate_instance
    from repro.core.policy import corais_encode, corais_score
    from repro.serving.fastpath import (DEFAULT_BUCKETS, DecisionFastPath,
                                        pad_instance)

    check(tuple(BUCKET_SIZES) == tuple(DEFAULT_BUCKETS),
          f"buckets {DEFAULT_BUCKETS} changed; update BUCKET_SIZES")
    rng = np.random.default_rng(seed)
    work = []  # (bucket, instance)
    for bucket, sizes in BUCKET_SIZES.items():
        for q, z in sizes:
            for _ in range(ROUNDS_PER_SIZE):
                inst = generate_instance(
                    rng, InstanceConfig(num_edges=q, num_requests=z))
                work.append((bucket, inst))

    @jax.jit
    def ref_scores(inst):
        with jax.default_matmul_precision("highest"):
            c, h, _ = corais_encode(params, pstate, inst, pcfg,
                                    training=False)
            return corais_score(params, c, h, inst["edge_mask"], pcfg,
                                backend="ref")

    # the reference scores each instance padded to its bucket (decisions
    # are mask-invariant), so it compiles once per bucket
    ref = [np.asarray(ref_scores(pad_instance(inst, *b))) for b, inst in work]

    for backend in ("xla", "pallas"):
        t0 = time.perf_counter()
        fp = DecisionFastPath(params, pstate, pcfg, backend=backend,
                              fused_decode=True, seed=seed)
        compile_ms = fp.warmup()
        check(set(compile_ms) == set(DEFAULT_BUCKETS), "not every bucket warm")
        if backend == "pallas":
            for bucket in DEFAULT_BUCKETS:
                inst = next(i for b, i in work if b == bucket)
                lane, packed = fp._stage(inst, bucket)
                compiled_with_kernel(lane.fn, jax.device_put(packed),
                                     fp._key0, what=f"pallas bucket {bucket}")
        equal = total = 0
        worst = 0.0
        for (bucket, inst), lp in zip(work, ref):
            assign = fp.decide(inst)
            q = int(inst["edge_mask"].sum())
            z = int(inst["req_mask"].sum())
            check(assign.shape == (z,), f"assignment shape {assign.shape}")
            check(bool(((assign >= 0) & (assign < q)).all()),
                  f"{backend} {bucket}: assignment outside the real edges")
            lp = lp[:z, :q]
            regret = lp.max(axis=1) - lp[np.arange(z), assign]
            check(np.isfinite(regret).all(), "non-finite reference score")
            worst = max(worst, float(regret.max()))
            equal += int((assign == lp.argmax(axis=1)).sum())
            total += z
        emit("decision_server", backend=backend, buckets=len(compile_ms),
             decisions=total, exact_equal_frac=f"{equal / total:.6f}",
             worst_regret=f"{worst:.6g}", regret_tol=REGRET_TOL,
             kernel_checked=backend == "pallas",
             wall_s=f"{time.perf_counter() - t0:.3f}")
        check(worst <= REGRET_TOL,
              f"{backend}: worst regret {worst} exceeds {REGRET_TOL}")


# -- phase 2: engine ---------------------------------------------------------


class _ReplayController:
    """Oracle-side controller that dispatches every request to the edge the
    array engine chose for it."""

    last_decision_time = 0.0

    def __init__(self, edge_of: dict):
        self.edge_of = edge_of

    def schedule(self, edges, pending, w, ct):
        return [(r, self.edge_of[r.rid]) for r in pending]


def _oracle_check(final, arr, i, seed, wl):
    import numpy as np

    from repro.serving import MultiEdgeSim, SimConfig

    mask = arr["mask"][i].ravel()
    rids = arr["rid"][i].ravel()[mask]
    edge = final["slot_edge"][i].ravel()
    check(bool((edge[mask] >= 0).all()), "engine left a request unassigned")
    fin_engine = final["slot_finish"][i].ravel()[mask]
    sim = MultiEdgeSim(SimConfig(num_edges=PAPER_EDGES, round_interval=DT,
                                 seed=seed, exec_noise=0.0, phi_oracle=True),
                       _ReplayController(dict(zip(rids.tolist(),
                                                  edge[mask].tolist()))))
    m = sim.drive(wl, until=ROUNDS * DT, run_until=1e5, seed=seed)
    check(m["completed"] == m["submitted"] == len(rids) > 0,
          f"oracle completed {m['completed']} of {len(rids)}")
    done = {r.rid: r.finish_time for e in sim.edges for r in e.completed}
    fin_oracle = np.array([done[r] for r in rids.tolist()])
    np.testing.assert_allclose(fin_engine, fin_oracle, rtol=FINISH_RTOL,
                               atol=FINISH_ATOL)
    np.testing.assert_allclose(fin_engine.max(), fin_oracle.max(),
                               rtol=FINISH_RTOL, atol=FINISH_ATOL)
    bounds = (np.arange(ROUNDS) + 1) * DT + 1e-6
    np.testing.assert_array_equal(
        (fin_engine[None, :] <= bounds[:, None]).sum(-1),
        (fin_oracle[None, :] <= bounds[:, None]).sum(-1))
    return float(np.abs(fin_engine - fin_oracle).max())


def _paper_batch(seed):
    from repro.serving import engine
    from repro.workloads import materialize_round_batch, scenario

    wl = scenario("uniform_iid", rate=PAPER_RATE)
    arr = materialize_round_batch(wl, PAPER_EDGES, ROUNDS, DT, ENGINE_BATCH,
                                  base_seed=seed)
    cfg = engine.EngineConfig(num_edges=PAPER_EDGES, num_rounds=ROUNDS,
                              round_interval=DT,
                              max_per_round=arr["mask"].shape[-1])
    states = engine.init_batch(cfg, range(seed, seed + ENGINE_BATCH))
    return wl, cfg, states, arr


def phase_engine(params, pstate, pcfg, seed):
    import jax
    import numpy as np

    from repro.core.inference import DecisionSpec
    from repro.serving import engine

    t0 = time.perf_counter()
    wl, cfg, states, arr = _paper_batch(seed)
    keys = jax.random.split(jax.random.PRNGKey(seed), ENGINE_BATCH)
    run = engine.make_rollout(cfg, engine.greedy_assign, batch=True)
    final, _ = run(states, arr, keys)
    final = jax.device_get(final)
    s = engine.summarize(final)
    check(s["completed"] == s["submitted"] == int(arr["mask"].sum()),
          f"greedy rollout completed {s['completed']} of {s['submitted']}")
    worst = max(_oracle_check(final, arr, i, seed + i, wl)
                for i in range(ORACLE_ELEMENTS))
    emit("engine", assign="greedy", edges=PAPER_EDGES, rounds=ROUNDS,
         batch=ENGINE_BATCH,
         arrivals_per_round=f"{arr['mask'].sum() / ENGINE_BATCH / ROUNDS:.2f}",
         completed=s["completed"], mean_response=f"{s['mean_response']:.6f}",
         oracle_elements=ORACLE_ELEMENTS,
         oracle_max_abs_finish_err=f"{worst:.3g}",
         wall_s=f"{time.perf_counter() - t0:.3f}")

    t0 = time.perf_counter()
    policy = engine.resolve_assign_fn(
        "policy", params=params, policy_state=pstate, policy_cfg=pcfg,
        spec=DecisionSpec(backend="pallas"))
    run_p = compiled_with_kernel(
        engine.make_rollout(cfg, policy, batch=True), states, arr, keys,
        what="pallas policy rollout")
    final_p, _ = run_p(states, arr, keys)
    sp = engine.summarize(jax.device_get(final_p))
    check(sp["completed"] == sp["submitted"] == s["submitted"],
          f"policy rollout completed {sp['completed']} of {sp['submitted']}")
    check(np.isfinite(sp["mean_response"]), "policy mean response not finite")
    emit("engine", assign="policy", score_backend="pallas",
         batch=ENGINE_BATCH, completed=sp["completed"],
         mean_response=f"{sp['mean_response']:.6f}", kernel_checked=True,
         wall_s=f"{time.perf_counter() - t0:.3f}")


# -- phase 3: trainer --------------------------------------------------------


def _train_setup(pcfg, seed, batch, epoch_len):
    """Paper-width policy, 10-edge engine config, and one epoch of K
    batches of initial cluster states and per-element keys."""
    import jax
    import numpy as np

    from repro.core.policy import corais_init
    from repro.core.train import (TemporalRLConfig, _cluster_seeds,
                                  _element_keys)
    from repro.serving import engine

    tcfg = TemporalRLConfig(
        policy=pcfg, engine=engine.EngineConfig(num_edges=PAPER_EDGES,
                                                num_rounds=ROUNDS,
                                                round_interval=DT),
        scenario="uniform_iid", batch_size=batch, lr=1e-4, seed=seed,
        device_episodes=True, epoch_len=epoch_len)
    params, pstate = corais_init(jax.random.PRNGKey(seed), pcfg)
    stacks = [engine.init_batch(tcfg.engine, _cluster_seeds(tcfg, b))
              for b in range(epoch_len)]
    sim0 = {k: np.stack([s[k] for s in stacks]) for k in stacks[0]}
    key = jax.random.PRNGKey(seed)
    ekeys = np.stack([np.asarray(_element_keys(key, b, batch))
                      for b in range(epoch_len)])
    return tcfg, params, pstate, sim0, ekeys


def phase_trainer(pcfg, seed):
    import dataclasses

    import jax
    import numpy as np

    from repro.core.train import make_temporal_epoch_step
    from repro.optim import adam_init

    t0 = time.perf_counter()
    batch, k = 16, 2
    tcfg, params, pstate, sim0, ekeys = _train_setup(
        dataclasses.replace(pcfg, score_backend="pallas"), seed, batch, k)
    before = jax.tree.map(np.asarray, params)
    step, adam_cfg = make_temporal_epoch_step(tcfg)
    opt = adam_init(params, adam_cfg)
    step = compiled_with_kernel(step, params, pstate, opt, sim0, ekeys,
                                what="pallas epoch step")
    params, opt, mets = step(params, pstate, opt, sim0, ekeys)
    mets = jax.device_get(mets)
    loss, gnorm = np.asarray(mets["loss"]), np.asarray(mets["grad_norm"])
    check(loss.shape == (k,) and np.isfinite(loss).all(), f"loss {loss}")
    check(np.isfinite(gnorm).all() and (gnorm > 0).all(), f"gnorm {gnorm}")
    moved = max(float(np.abs(np.asarray(a) - b).max())
                for a, b in zip(jax.tree.leaves(params),
                                jax.tree.leaves(before)))
    check(moved > 0, "parameters did not move")
    emit("trainer", score_backend="pallas", batch=batch, epoch_len=k,
         loss=[float(x) for x in loss], grad_norm=[float(x) for x in gnorm],
         max_param_change=f"{moved:.3g}", kernel_checked=True,
         wall_s=f"{time.perf_counter() - t0:.3f}")


# -- four chips --------------------------------------------------------------


def _on_all(tree, devices, what):
    import jax
    for leaf in jax.tree.leaves(tree):
        got = {s.device for s in leaf.addressable_shards}
        check(got == set(devices), f"{what}: shards on {got}, not all devices")


def phase_fleet(seed, devices):
    import jax
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_fleet_mesh
    from repro.serving import engine
    from repro.serving.fleet import make_fleet_rollout

    t0 = time.perf_counter()
    _, cfg, states, arr = _paper_batch(seed)
    keys = np.asarray(jax.random.split(jax.random.PRNGKey(seed),
                                       ENGINE_BATCH))
    mesh = make_fleet_mesh(len(devices))
    run = engine.make_rollout(cfg, engine.greedy_assign, batch=True)
    ref = engine.partials_to_summary(engine.summarize_partials(
        run(states, arr, keys)[0]))
    shard = NamedSharding(mesh, P("fleet"))
    placed = jax.device_put((states, arr, keys), shard)
    _on_all(placed, devices, "fleet inputs")
    parts = make_fleet_rollout(cfg, engine.greedy_assign, mesh)(*placed)
    _on_all(parts, devices, "fleet partials")
    got = engine.partials_to_summary(parts)
    for k in ("completed", "submitted", "per_edge_completed"):
        check(got[k] == ref[k], f"fleet {k}: {got[k]} != {ref[k]}")
    for k in ("mean_response", "max_response", "makespan",
              "transferred_frac", "p50_response", "p95_response"):
        np.testing.assert_allclose(got[k], ref[k], rtol=FLEET_RTOL,
                                   atol=1e-7, err_msg=k)
    emit("fleet", shards=len(devices), batch=ENGINE_BATCH,
         completed=got["completed"], mean_response=f"{got['mean_response']:.6f}",
         ref_mean_response=f"{ref['mean_response']:.6f}",
         wall_s=f"{time.perf_counter() - t0:.3f}")


def phase_sharded_trainer(pcfg, seed, devices):
    import dataclasses

    import jax
    import numpy as np

    from repro.core.train import make_temporal_epoch_step
    from repro.launch.mesh import make_fleet_mesh
    from repro.optim import AdamConfig, adam_init

    t0 = time.perf_counter()
    batch, k = 16, 2
    # layer norm keeps elements independent, so per-shard and global-batch
    # normalization agree (tests/train_child.py, the batchnorm caveat)
    pcfg = dataclasses.replace(pcfg, norm="layer", score_backend="pallas")
    tcfg, params, pstate, sim0, ekeys = _train_setup(pcfg, seed, batch, k)
    adam = AdamConfig(lr=tcfg.lr, eps=1e-3)
    opt = adam_init(params, adam)
    single, _ = make_temporal_epoch_step(tcfg, adam, donate=False)
    sharded, _ = make_temporal_epoch_step(tcfg, adam, donate=False,
                                          mesh=make_fleet_mesh(len(devices)))
    p1, o1, m1 = single(params, pstate, opt, sim0, ekeys)
    p2, o2, m2 = sharded(params, pstate, opt, sim0, ekeys)
    _on_all(p2, devices, "sharded params")
    diff = max(float(np.abs(np.asarray(a) - np.asarray(b)).max())
               for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
    loss1 = np.asarray(m1["loss"])
    loss2 = np.asarray(m2["loss"])
    check(np.isfinite(loss2).all(), f"sharded loss {loss2}")
    # the tolerances of tests/train_child.py (Adam eps=1e-3 keeps the
    # psum-reassociation noise far below them)
    np.testing.assert_allclose(loss2, loss1, rtol=1e-4, atol=1e-5)
    check(diff <= 1e-5, f"sharded vs single params differ by {diff}")
    emit("sharded_trainer", shards=len(devices), batch=batch, epoch_len=k,
         loss=[float(x) for x in loss2], ref_loss=[float(x) for x in loss1],
         max_param_diff=f"{diff:.3g}",
         wall_s=f"{time.perf_counter() - t0:.3f}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    try:
        from repro import platform
    except ImportError as e:
        print(f"chip_smoke: cannot import the scheduler from {ROOT / 'src'}: "
              f"{e}", file=sys.stderr)
        return 2
    platform.setup_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {devices[0].platform}); "
              f"refusing to run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    devices = devices[:args.chips]

    from repro.core.policy import PolicyConfig

    run_phases(PolicyConfig(), args.seed, devices)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


def run_phases(pcfg, seed: int, devices):
    """The one-chip phases, or with several devices the multi-chip ones.
    Any failure raises."""
    import jax

    from repro.core.policy import corais_init

    params, pstate = corais_init(jax.random.PRNGKey(seed), pcfg)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    emit("setup", device=devices[0].device_kind, count=len(devices),
         d_model=pcfg.d_model, params=n_params, seed=seed)
    if len(devices) == 1:
        phase_decision_server(params, pstate, pcfg, seed)
        phase_engine(params, pstate, pcfg, seed)
        phase_trainer(pcfg, seed)
    else:
        phase_fleet(seed, devices)
        phase_sharded_trainer(pcfg, seed, devices)


if __name__ == "__main__":
    sys.exit(main())
