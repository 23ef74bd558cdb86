"""Open-loop decision serving through the scheduler's fast path.

Rounds arrive on a Poisson schedule fixed by the seed, each a snapshot
drawn from a pool made at set-up. The loop submits a round when it is due
(``DecisionFastPath.submit``: bucket padding, staging, transfer, dispatch)
and collects the oldest answer (``.result``) otherwise; at most two rounds
are outstanding, the fast path's double buffer. A round's latency runs from
the time it was due to the time its assignment is on the host, so a stall
delays every round queued behind it.

After the window: every round's answer must have come and be well formed
(one edge in range for each request); a sample of the served rounds, drawn
from the seed with the largest round in it, is scored by the plain
reference at full float32 precision; the regret of a served assignment
(reference score of the best edge minus that of the chosen one) is
summarized and held to the traffic's ``limits``. A round answered after
``late_after_s`` is late, not failed: the latency counts the wait.
"""
from __future__ import annotations

import collections
import gc
import sys
import time

import numpy as np

from benchlib import gen, harness, reference, weights
from benchlib import trace as tr


def window(fp, pool, order, due, seconds, *, trace=False, drain_s=60.0):
    """Drive the fast path open loop over ``due`` (s from the start).
    Returns per-round arrays (times are time.perf_counter())."""
    n = len(due)
    t0 = time.perf_counter()
    abs_due = t0 + due
    sub0, sub1, res1 = (np.full(n, np.nan) for _ in range(3))
    assign = [None] * n
    inflight = collections.deque()
    i = 0
    stop = t0 + seconds + drain_s
    with harness.span(trace, "bench.window"):
        while i < n or inflight:
            now = time.perf_counter()
            if now > stop:
                break
            if i < n and abs_due[i] <= now and len(inflight) < 2:
                with harness.span(trace, "bench.submit"):
                    sub0[i] = time.perf_counter()
                    inflight.append((i, fp.submit(pool[order[i]])))
                    sub1[i] = time.perf_counter()
                i += 1
            elif inflight:
                j, h = inflight.popleft()
                with harness.span(trace, "bench.result"):
                    a = fp.result(h)
                    res1[j] = time.perf_counter()
                assign[j] = a
            else:
                with harness.span(trace, "bench.wait"):
                    left = abs_due[i] - time.perf_counter()
                    if left > 5e-4:
                        time.sleep(left - 3e-4)
                    while time.perf_counter() < abs_due[i]:
                        pass
    return {"t0": t0, "due": abs_due, "sub0": sub0, "sub1": sub1,
            "res1": res1, "assign": assign}


def unanswered_or_malformed(pool, order, rec) -> int:
    """Rounds whose answer never came, or is not one edge in range for
    each of the round's requests."""
    bad = 0
    for j, a in enumerate(rec["assign"]):
        inst = pool[order[j]]
        q, z = inst["edge_mask"].shape[0], inst["req_mask"].shape[0]
        a = None if a is None else np.asarray(a)
        bad += a is None or a.shape != (z,) or not ((a >= 0) & (a < q)).all()
    return int(bad)


def check(params, state, pol, pool, order, rec, bucket, n_check, seed,
          control=False) -> dict:
    """Regret of the served assignments of a seeded sample of rounds (with
    the largest in it) against the reference: a decision's regret is the
    reference score of the best edge minus that of the chosen one. With
    ``control`` the choices are those of the reference with float8 (e4m3)
    matmul operands instead. Returns
    worst_regret, mean_regret (over decisions), flip_frac (share not the
    reference's first choice), checked (decisions), bad (rounds that never
    came or came malformed, of all the window's rounds)."""
    import jax

    answered = [j for j, a in enumerate(rec["assign"]) if a is not None]
    out = {"worst_regret": float("inf"), "mean_regret": float("inf"),
           "flip_frac": 1.0, "checked": 0,
           "bad": unanswered_or_malformed(pool, order, rec)}
    if not answered:
        return out
    rng = gen.rng_for(seed, 0xC4)
    pick = set(rng.choice(answered, min(n_check, len(answered)),
                          replace=False).tolist())
    pick.add(max(answered, key=lambda j: pool[order[j]]["req_mask"].shape[0]))
    pick = sorted(pick)
    kw = dict(heads=pol["num_heads"], tanh_clip=pol["tanh_clip"],
              feature_scale=pol["feature_scale"])
    ref_fn = reference.batched_scores(**kw)
    ctl_fn = reference.batched_scores(**kw, control=True) if control else None
    regrets, flips = [], 0
    for b0 in range(0, len(pick), 16):
        rows = pick[b0:b0 + 16]
        n_rows = len(rows)
        rows = rows + [rows[-1]] * (16 - n_rows)  # one compiled shape
        batch = gen.stack([gen.pad_to(pool[order[j]], *bucket) for j in rows])
        batch = jax.device_put(batch)
        s = np.asarray(ref_fn(params, state, batch))
        c = np.asarray(ctl_fn(params, state, batch)) if control else None
        for r, j in enumerate(rows[:n_rows]):
            inst = pool[order[j]]
            q, z = inst["edge_mask"].shape[0], inst["req_mask"].shape[0]
            a = np.asarray(rec["assign"][j])
            if control:
                a = c[r, :z, :q].argmax(1)
            if a.shape != (z,) or not ((a >= 0) & (a < q)).all():
                continue  # counted in "bad"
            sr = s[r, :z, :q]
            regrets.append(sr.max(1) - sr[np.arange(z), a])
            flips += int((a != sr.argmax(1)).sum())
    if regrets:
        reg = np.concatenate(regrets)
        out.update(worst_regret=float(reg.max()), mean_regret=float(reg.mean()),
                   flip_frac=flips / reg.size, checked=int(reg.size))
    return out


def setup(ctx: harness.Context):
    """Weights, traffic and a warm fast path; everything before the window."""
    import jax
    from repro.serving.fastpath import DecisionFastPath

    cfg, trf = ctx.cell.config, ctx.cell.traffic
    pol = weights.widths(cfg, ctx.policy_override)
    with harness.phase(ctx, "weights"):
        params, state = weights.make_policy(ctx.seed, pol)
        jax.block_until_ready(params)
    q, z_lo, z_hi = trf["edges"], trf["requests_low"], trf["requests_high"]
    with harness.phase(ctx, "traffic"):
        pool = gen.snapshot_pool(ctx.seed, trf["pool"], q, z_lo, z_hi,
                                 cfg["law"])
        due = gen.poisson_schedule(ctx.seed, trf["rate_per_s"], ctx.seconds)
        order = gen.pool_order(ctx.seed, len(pool), len(due))
    with harness.phase(ctx, "program"):
        fp = DecisionFastPath(params, state,
                              weights.program_config(pol, trf["backend"]),
                              backend=trf["backend"], fused_decode=True,
                              mode="greedy", seed=0)
        bucket = fp.bucket_for(q, z_hi)
        if fp.bucket_for(q, z_lo) != bucket:
            raise ValueError(f"traffic {z_lo}..{z_hi} requests spans two "
                             f"buckets")
        fp.warmup([bucket])
        for inst in pool[:trf["warm_rounds"]]:
            fp.decide(inst)
    return pol, params, state, pool, due, order, fp, bucket


def run(ctx: harness.Context) -> harness.Outcome:
    import jax

    trf = ctx.cell.traffic
    pol, params, state, pool, due, order, fp, bucket = setup(ctx)
    setup_s = time.monotonic() - ctx.t_process_start

    if ctx.trace:
        ctx.trace_dir.mkdir(parents=True, exist_ok=True)
        jax.profiler.start_trace(str(ctx.trace_dir))
    gc.collect()
    gc.freeze()
    try:
        with harness.CompileCounter() as compiles:
            rec = window(fp, pool, order, due, ctx.seconds, trace=ctx.trace)
    finally:
        if ctx.trace:
            jax.profiler.stop_trace()
    stats = jax.devices()[0].memory_stats() or {}
    peak_bytes = int(stats.get("peak_bytes_in_use", 0))

    lat = rec["res1"] - rec["due"]
    done = np.isfinite(lat)
    late = int((lat[done] > trf["late_after_s"]).sum())
    lat_ms = lat[done] * 1e3
    e2e = {"setup_s": setup_s,
           "decision_p50_ms": float(np.percentile(lat_ms, 50)),
           "decision_p99_ms": float(np.percentile(lat_ms, 99))}

    got = check(params, state, pol, pool, order, rec, bucket,
                trf["check_rounds"], ctx.seed)
    checks = {k: {"value": got[k], "limit": v}
              for k, v in trf["limits"].items()}
    checks["unanswered_or_malformed"] = {"value": got["bad"], "limit": 0}
    correct = got["checked"] > 0 and all(
        c["value"] <= c["limit"] for c in checks.values())
    checked = got["checked"]

    # how late the generator ran: submit start past due, for rounds that
    # found nothing outstanding (a queued round waits by design)
    idle = np.isfinite(rec["sub0"])
    gen_late = (rec["sub0"] - rec["due"])[idle]
    print(f"serve: rounds={len(due)} answered={int(done.sum())} "
          f"late={late} checked_decisions={checked} "
          f"submit_late_p50_ms={np.percentile(gen_late, 50) * 1e3:.4f} "
          f"p50_ms={e2e['decision_p50_ms']:.4f} "
          f"p99_ms={e2e['decision_p99_ms']:.4f} "
          f"rate={len(due) / ctx.seconds:.1f}/s "
          f"compiles_in_window={compiles.count}", file=sys.stderr)

    calls = [(pool[order[j]]["edge_mask"].shape[0],
              pool[order[j]]["req_mask"].shape[0])
             for j in range(len(due)) if done[j]]
    data = {"pol": pol, "calls": calls,
            "submit_s": (rec["sub1"] - rec["sub0"])[done],
            "result_s": (rec["res1"] - rec["sub1"])[done],
            "turnaround_s": (rec["res1"] - rec["sub0"])[done],
            "rounds": len(due), "late": late,
            "device_kind": jax.devices()[0].device_kind}
    out = harness.Outcome(correct=correct, attempted=len(due),
                          failed=got["bad"],
                          end_to_end=e2e, checks=checks,
                          memory_peak_bytes=peak_bytes, layer_data=data)
    if ctx.trace:
        t = tr.load(str(ctx.trace_dir))
        lo, hi = t.window()
        data.update(trace=t, lo=lo, hi=hi)
        out.busy_s = tr.busy_s(t, lo, hi)
        out.window_s = (hi - lo) * tr.NS
        out.breakdown = {"device_ops": tr.top_ops(tr.ops_in(t, lo, hi)),
                         "idle_gaps": tr.idle_gaps(t, lo, hi)}
    return out


def control_readings(ctx: harness.Context) -> dict:
    """The numbers ``correct`` compares, for the program's served
    assignments and for the float8-operand control, after a short window at the
    cell's own load (bench/control.py)."""
    trf = ctx.cell.traffic
    pol, params, state, pool, due, order, fp, bucket = setup(ctx)
    rec = window(fp, pool, order, due, ctx.seconds)
    args = (params, state, pol, pool, order, rec, bucket, trf["check_rounds"],
            ctx.seed)
    return {"rounds": len(due), "program": check(*args),
            "control": check(*args, control=True)}
