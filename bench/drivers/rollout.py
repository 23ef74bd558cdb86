"""Closed-loop batched policy-evaluation rollouts through the scheduler's
array engine (``engine.make_rollout(..., batch=True)``).

Set-up makes a pool of arrival batches and clusters from the seed; the
window calls the compiled rollout back to back over the pool, each call a
batch of cluster instances rolled through every round with the policy
(fused decode, greedy) deciding each round. The rate is the simulated
requests completed by the calls finished in the window over the time from
the window start to the end of the last of them.

After the window: a seeded sample of (call, instance) pairs, with the
busiest instance in it, is replayed by the plain reference physics
(float32 times, the engine's stated precision) with the engine's own
assignments. Finish times are compared, and each round's decisions are
judged by the reference policy scores of the snapshot the replay rebuilt
(regret, as in the serve driver).
"""
from __future__ import annotations

import gc
import sys
import time

import numpy as np

from benchlib import gen, harness, physics, reference, weights
from benchlib import trace as tr

def setup(ctx: harness.Context):
    import jax
    from repro.serving import engine

    cfg, trf = ctx.cell.config, ctx.cell.traffic
    pol = weights.widths(cfg, ctx.policy_override)
    with harness.phase(ctx, "weights"):
        params, state = weights.make_policy(ctx.seed, pol)
        jax.block_until_ready(params)
    law = cfg["cluster"]
    q, R, A, B = trf["edges"], trf["rounds"], trf["max_per_round"], trf["batch"]
    dt = trf["round_interval_s"]
    ecfg = engine.EngineConfig(num_edges=q, replicas_high=law["replicas_high"],
                               ct=law["ct"], round_interval=dt, num_rounds=R,
                               max_per_round=A)
    with harness.phase(ctx, "traffic"):
        rng = gen.rng_for(ctx.seed, 0x20)
        pool = []
        for _ in range(trf["pool_batches"]):
            cls = [gen.cluster(rng, q, law) for _ in range(B)]
            arrs = [gen.round_arrivals(rng, q, R, dt, trf["rate_per_s"], A)
                    for _ in range(B)]
            st = engine.init_batch(ecfg, range(B))
            lanes = np.arange(ecfg.lane_width)[None, None, :]
            reps = np.stack([c["replicas"] for c in cls])
            st["coords"] = np.stack([c["coords"] for c in cls]).astype(
                np.float32)
            st["w"] = np.stack([c["w"] for c in cls]).astype(np.float32)
            st["phi_true"] = np.stack([np.stack([c["a"], c["b"]], -1)
                                       for c in cls]).astype(np.float32)
            st["phi_est"] = st["phi_true"].copy()
            st["replicas"] = reps.astype(np.float32)
            st["lane_free"] = np.where(lanes < reps[:, :, None], 0.0,
                                       engine.INF).astype(np.float32)
            arr = {k: np.stack([a[k] for a in arrs])
                   for k in ("t", "src", "size", "mask")}
            keys = jax.random.split(weights.seed_key(ctx.seed), B)
            pool.append({"clusters": cls, "arr": arr, "state": st,
                         "keys": keys, "dev": jax.device_put((st, arr, keys)),
                         "arrivals": int(arr["mask"].sum())})

    with harness.phase(ctx, "program"):
        assign = engine.resolve_assign_fn(
            trf["assign"], params=params, policy_state=state,
            policy_cfg=weights.program_config(pol, trf["backend"]))
        run = engine.make_rollout(ecfg, assign, batch=True)
        for p in pool[:1]:  # compile and warm the one shape the window runs
            jax.block_until_ready(run(*p["dev"]))
    return pol, params, state, ecfg, pool, run


def window(run, pool, seconds, trace_calls=0, stop_trace=None):
    """Calls back to back until ``seconds`` have passed; returns the calls
    finished (pool index, start, end, completed, outputs kept for the
    check). The first ``trace_calls`` calls run under host spans, and
    ``stop_trace`` is called when they are done: the engine's scan steps
    are millions of device operations a call, and a whole window of them
    overflows the profiler's event buffer (at ~6.3M events, ~8 calls)."""
    calls = []
    t0 = time.perf_counter()
    k = 0
    while True:
        p = pool[k % len(pool)]
        traced = k < trace_calls
        t1 = time.perf_counter()
        with harness.span(traced, "bench.call"):
            with harness.span(traced, "bench.dispatch"):
                final, infos = run(*p["dev"])
            with harness.span(traced, "bench.fetch"):
                done = int(np.asarray(final["completed"]).sum())
        t2 = time.perf_counter()
        calls.append({"pool": k % len(pool), "start": t1, "end": t2,
                      "completed": done,
                      "out": (final["slot_finish"], final["slot_edge"],
                              infos["assign"])})
        k += 1
        if k == trace_calls:
            stop_trace()
        if t2 - t0 >= seconds:
            return t0, calls


def check(params, state, pol, pool, calls, trf, ct, seed,
          control=False) -> dict:
    """Replay a seeded sample of instances through the reference. Returns
    finish_err_s (largest |engine - reference| finish time), worst_regret,
    mean_regret, flip_frac, unfinished (requests the engine left without a
    finish), edge_mismatch (slots whose edge is not the round's decision),
    checked (instances). With ``control`` the lower-precision twins stand
    in: the physics in bfloat16 times, and as each round's decisions the
    first choices of the policy reference with float8 (e4m3) operands."""
    import jax

    B = trf["batch"]
    rng = gen.rng_for(seed, 0xC5)
    n = min(trf["check_instances"], len(calls) * B)
    flat = rng.choice(len(calls) * B, n, replace=False).tolist()
    busiest = max(range(len(calls) * B), key=lambda i: int(
        pool[calls[i // B]["pool"]]["arr"]["mask"][i % B].sum()))
    picks = sorted(set(flat) | {busiest})
    dt = trf["round_interval_s"]
    out = {"finish_err_s": 0.0, "unfinished": 0, "edge_mismatch": 0,
           "worst_regret": 0.0, "mean_regret": 0.0, "flip_frac": 0.0,
           "checked": 0}
    snaps, decisions = [], []
    for i in picks:
        c, b = calls[i // B], i % B
        p = pool[c["pool"]]
        fin, edge, asg = (np.asarray(x[b]) for x in c["out"])
        arr = {k: v[b] for k, v in p["arr"].items()}
        mask = arr["mask"]
        fin = fin.reshape(mask.shape)
        edge = edge.reshape(mask.shape)
        out["edge_mismatch"] += int((edge[mask] != asg[mask]).sum())
        out["unfinished"] += int((fin[mask] >= 1e29).sum())
        ref = physics.replay(p["clusters"][b], arr, asg, dt, ct)
        got = (physics.replay(p["clusters"][b], arr, asg, dt, ct,
                              dtype=jax.numpy.bfloat16)["finish"]
               if control else fin)
        err = np.abs(got[mask].astype(np.float64) - ref["finish"][mask])
        out["finish_err_s"] = max(out["finish_err_s"], float(err.max()))
        snaps += ref["snapshots"]
        decisions += list(asg)
        out["checked"] += 1
    kw = dict(heads=pol["num_heads"], tanh_clip=pol["tanh_clip"],
              feature_scale=pol["feature_scale"])
    ref_fn = reference.batched_scores(**kw)
    ctl_fn = reference.batched_scores(**kw, control=True) if control else None
    regrets, flips = [], 0
    step = trf["rounds"]
    for s0 in range(0, len(snaps), step):
        batch = jax.device_put(gen.stack(snaps[s0:s0 + step]))
        s = np.asarray(ref_fn(params, state, batch))
        cs = np.asarray(ctl_fn(params, state, batch)) if control else None
        for r in range(len(snaps[s0:s0 + step])):
            m = snaps[s0 + r]["req_mask"]
            a = cs[r].argmax(1) if control else decisions[s0 + r]
            sr = s[r][m]
            a = np.asarray(a)[m]
            regrets.append(sr.max(1) - sr[np.arange(sr.shape[0]), a])
            flips += int((a != sr.argmax(1)).sum())
    reg = np.concatenate(regrets)
    out.update(worst_regret=float(reg.max()), mean_regret=float(reg.mean()),
               flip_frac=flips / reg.size)
    return out


def run(ctx: harness.Context) -> harness.Outcome:
    import jax

    trf = ctx.cell.traffic
    pol, params, state, ecfg, pool, roll = setup(ctx)
    setup_s = time.monotonic() - ctx.t_process_start

    tracing = [ctx.trace]

    def stop_trace():
        if tracing[0]:
            jax.profiler.stop_trace()
            tracing[0] = False

    if ctx.trace:
        ctx.trace_dir.mkdir(parents=True, exist_ok=True)
        jax.profiler.start_trace(str(ctx.trace_dir))
    gc.collect()
    gc.freeze()
    try:
        with harness.CompileCounter() as compiles:
            t0, calls = window(roll, pool, ctx.seconds,
                               trf["trace_calls"] if ctx.trace else 0,
                               stop_trace)
    finally:
        stop_trace()
    stats = jax.devices()[0].memory_stats() or {}
    peak_bytes = int(stats.get("peak_bytes_in_use", 0))

    submitted = sum(pool[c["pool"]]["arrivals"] for c in calls)
    completed = sum(c["completed"] for c in calls)
    span = calls[-1]["end"] - t0
    e2e = {"setup_s": setup_s, "rollout_requests_per_s": completed / span}

    got = check(params, state, pol, pool, calls, trf,
                ctx.cell.config["cluster"]["ct"], ctx.seed)
    checks = {k: {"value": got[k], "limit": v}
              for k, v in trf["limits"].items()}
    checks["unfinished"] = {"value": got["unfinished"], "limit": 0}
    checks["edge_mismatch"] = {"value": got["edge_mismatch"], "limit": 0}
    correct = got["checked"] > 0 and all(
        c["value"] <= c["limit"] for c in checks.values())
    print(f"rollout: calls={len(calls)} submitted={submitted} "
          f"completed={completed} call_s_median="
          f"{np.median([c['end'] - c['start'] for c in calls]):.6f} "
          f"checked_instances={got['checked']} "
          f"compiles_in_window={compiles.count}", file=sys.stderr)

    # per simulated round of the traced calls (one batched decision, one
    # kernel call), the real (edges, requests) of each instance's decision
    traced = calls[:trf["trace_calls"]]
    rounds = []
    for c in traced:
        z = pool[c["pool"]]["arr"]["mask"].sum(-1)      # (batch, rounds)
        rounds += [[(ecfg.num_edges, int(n)) for n in z[:, r]]
                   for r in range(z.shape[1])]
    data = {"pol": pol, "device_kind": jax.devices()[0].device_kind,
            "rounds": rounds, "calls": len(traced),
            "batched_rounds": len(traced) * trf["rounds"],
            "window_host_s": traced[-1]["end"] - traced[0]["start"]}
    out = harness.Outcome(correct=correct, attempted=submitted,
                          failed=submitted - completed, end_to_end=e2e,
                          checks=checks, memory_peak_bytes=peak_bytes,
                          layer_data=data)
    if ctx.trace:
        t = tr.load(str(ctx.trace_dir))
        lo, hi = t.window("bench.call")
        data.update(trace=t, lo=lo, hi=hi)
        out.busy_s = tr.busy_s(t, lo, hi)
        out.window_s = (hi - lo) * tr.NS
        out.breakdown = {"device_ops": tr.top_ops(tr.ops_in(t, lo, hi)),
                         "idle_gaps": tr.idle_gaps(t, lo, hi)}
    return out



def control_readings(ctx: harness.Context) -> dict:
    """The numbers ``correct`` compares, for the program's rollouts and for
    the control (bfloat16 physics, float8-operand policy), after a short
    window (bench/control.py)."""
    trf = ctx.cell.traffic
    pol, params, state, ecfg, pool, roll = setup(ctx)
    _, calls = window(roll, pool, ctx.seconds)
    args = (params, state, pol, pool, calls, trf,
            ctx.cell.config["cluster"]["ct"], ctx.seed)
    return {"calls": len(calls), "program": check(*args),
            "control": check(*args, control=True)}
