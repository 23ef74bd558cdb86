"""Scenario sweep: every registered workload scenario x scheduler backend.

Drives :meth:`MultiEdgeSim.drive` with each named scenario from the
workload registry against each scheduler backend and writes a JSON report
(per-cell completion/latency/decision metrics plus a per-scenario winner).
This is the scenario-diversity counterpart of the paper's Table II, which
only covers the i.i.d. uniform regime.

Run:  PYTHONPATH=src python benchmarks/scenario_sweep.py
      PYTHONPATH=src python benchmarks/scenario_sweep.py \\
          --backends greedy,local,random,corais --batches 800
      PYTHONPATH=src python benchmarks/scenario_sweep.py \\
          --backends greedy,batched-greedy,batched-local
      # policy-vs-baseline rollout comparison on paired engine episodes:
      PYTHONPATH=src python benchmarks/scenario_sweep.py \\
          --backends batched-local,batched-greedy,batched-corais,batched-corais-temporal

``corais`` trains (or loads a cached) policy via benchmarks.common first;
the heuristic backends need no training and finish in seconds. A
``batched-*`` backend runs the same scenario through the array-native
engine (repro.serving.engine, online phi fitting on) instead of the
event-driven simulator — same cluster seed and arrival stream, so its cells
are directly comparable to the event-driven columns.
``batched-corais-temporal`` selects the temporal policy (REINFORCE on
whole engine rollouts) instead of the static-trained one, so its column
against ``batched-corais`` / ``batched-greedy`` / ``batched-local`` is the
ROADMAP's policy-vs-baseline rollout benchmark.

Chaos scenarios (``chaos-*``, any scenario registered with a FaultSpec)
run fault-injected: batched cells fold the materialized fault trajectory
into the arrival batch (``resilience.faults.attach_fault_batch``),
event-driven cells schedule the identical fail/recover/straggle timeline
into the heap (``schedule_into_sim``), and every cell reports shed rate
and SLO-violation fraction next to the response percentiles. The extra
fault-matrix column is ``batched-corais-admit``: the static-trained
CoRaiS dispatch plus an admission head trained per scenario on
fault-injected episodes (dispatch frozen during that training, so
against ``batched-corais`` the column isolates what learned admission
adds under overload and failures).

  # resilience fault matrix (writes results/chaos_sweep.json):
  PYTHONPATH=src python benchmarks/scenario_sweep.py --chaos

Edge-cloud scenarios (``cloud-*``, any scenario registered with a
CloudSpec) run with the elastic cloud tier and per-edge service caches
threaded into both engines, and their cells carry deadline-miss /
cache-hit / cloud-offload columns plus a per-scenario deadline winner.
The extra column is ``batched-corais-cloud``: the tier-feature policy
temporal-trained against deadline misses on the miss-heavy
cloud-cache-churn scenario (benchmarks.common.get_cloud_policy) and
reused unchanged on every other scenario, so against ``batched-corais``
(cache-oblivious dispatch) and ``batched-greedy`` it isolates what the
deadline/cache/tier features buy:

  PYTHONPATH=src python benchmarks/scenario_sweep.py \\
      --scenarios cloud-cache-churn,cloud-burst-offload \\
      --backends batched-greedy,batched-corais,batched-corais-cloud
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

# `python benchmarks/scenario_sweep.py` puts benchmarks/ (not the repo
# root) on sys.path; the lazy `benchmarks.common` imports below need the
# root on it to resolve the package.
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

import jax

from repro.platform import setup_compile_cache
from repro.resilience import faults as faults_lib
from repro.serving import (ASSIGN_FNS, CentralController, EngineConfig,
                           MultiEdgeSim, SimConfig, init_batch,
                           make_rollout, resolve_assign_fn, summarize)
from repro.workloads import (list_scenarios, materialize_round_batch,
                             materialize_rounds, scenario,
                             scenario_cloud_spec, scenario_fault_spec)

REPORT_SCHEMA = "corais.scenario_sweep.v3"
DEFAULT_SLO = 3.0  # response-time SLO for the fault-matrix columns


def _make_controller(backend: str, num_edges: int, batches: int,
                     z_pad: int) -> CentralController:
    if backend in ("corais", "corais-sample"):
        from benchmarks.common import get_trained_policy
        params, state, cfg = get_trained_policy(num_edges, 50, batches,
                                                verbose=False)
        return CentralController(scheduler=backend, policy_params=params,
                                 policy_state=state, policy_cfg=cfg.policy,
                                 z_pad=z_pad)
    return CentralController(scheduler=backend)


#: batched-* inner names that resolve to a trained policy AssignFn:
#: static-trained (paper §IV-B i.i.d. snapshots) greedy/sampling decode,
#: the temporal policy trained on whole engine rollouts (the
#: policy-vs-baseline rollout comparison against batched-greedy /
#: batched-local on paired episodes), and corais-admit — the same
#: static-trained dispatch plus an admission head trained per scenario on
#: fault-injected episodes (frozen dispatch, so the column isolates what
#: admission adds). corais-cloud is the deadline/cache-aware variant:
#: tier features on, temporal-trained against deadline misses on
#: cloud-cache-churn (benchmarks.common.get_cloud_policy, one shared
#: column), so on cloud-* scenarios its cell against batched-corais
#: isolates what the tier/cache/deadline features buy over the
#: cache-oblivious dispatch.
POLICY_BACKENDS = ("corais", "corais-sample", "corais-temporal", "policy",
                   "corais-admit", "corais-cloud")


def _engine_assign_fn(inner: str, num_edges: int, batches: int,
                      scenario_name: str = "uniform_iid"):
    if inner in POLICY_BACKENDS:
        admission = False
        if inner == "corais-admit":
            from benchmarks.common import get_resilient_policy
            admission = True
            params, state, cfg = get_resilient_policy(
                num_edges, scenario_name=scenario_name,
                slo=DEFAULT_SLO, verbose=False)
            mode = "greedy"
        elif inner == "corais-cloud":
            # one shared column: the policy temporal-trained on
            # cloud-cache-churn (the miss-heavy scenario), reused on the
            # other scenarios so its cloud-burst-offload cell doubles as
            # a generalization check rather than retraining per scenario.
            # Sampled decode: episode REINFORCE trains the stochastic
            # policy, and per-round queue depth is not a request feature,
            # so argmax herds a round's identical-looking requests onto
            # one node — sampling realizes the load-spreading mixture the
            # training signal actually scored.
            from benchmarks.common import get_cloud_policy
            params, state, cfg = get_cloud_policy(num_edges, verbose=False)
            mode = "sample"
        elif inner == "corais-temporal":
            from benchmarks.common import get_temporal_policy
            params, state, cfg = get_temporal_policy(num_edges, batches,
                                                     verbose=False)
            mode = "greedy"
        else:
            from benchmarks.common import get_trained_policy
            params, state, cfg = get_trained_policy(num_edges, 50, batches,
                                                    verbose=False)
            mode = "sample" if inner == "corais-sample" else "greedy"
        return resolve_assign_fn("policy", params=params, policy_state=state,
                                 policy_cfg=cfg.policy, mode=mode,
                                 admission=admission)
    try:
        return resolve_assign_fn(inner)
    except ValueError:
        known = sorted(set(ASSIGN_FNS) - {"policy"}) + list(POLICY_BACKENDS)
        raise ValueError(
            f"no batched-engine backend {inner!r}; supported: "
            f"{', '.join('batched-' + k for k in known)}") from None


def _run_batched(backend: str, name: str, *, num_edges: int, until: float,
                 seed: int, batches: int, slo: float = DEFAULT_SLO) -> dict:
    """One batched-engine cell (batch of 1 rollout, paired with the
    event-driven cells by seed and arrival stream). Scenarios registered
    with a FaultSpec run fault-injected, and their cells carry the shed /
    SLO columns of the fault matrix."""
    inner = backend.split("-", 1)[1]
    interval = SimConfig().round_interval
    rounds = max(1, int(round(until / interval)))
    arrivals = materialize_round_batch(scenario(name), num_edges, rounds,
                                       interval, 1, base_seed=seed)
    fspec = scenario_fault_spec(name)
    if fspec is not None:
        arrivals = faults_lib.attach_fault_batch(arrivals, fspec, num_edges,
                                                 seeds=[seed])
    cloud, cache = scenario_cloud_spec(name)
    cfg = EngineConfig(num_edges=num_edges, num_rounds=rounds,
                       round_interval=interval, learn_phi=True,
                       max_per_round=arrivals["mask"].shape[-1],
                       cloud=cloud, cache=cache)
    state0 = init_batch(cfg, [seed])
    run = make_rollout(cfg, _engine_assign_fn(inner, num_edges, batches, name),
                       batch=True)
    keys = jax.random.split(jax.random.PRNGKey(seed), 1)
    jax.block_until_ready(run(state0, arrivals, keys))  # compile
    t0 = time.time()
    final, _ = run(state0, arrivals, keys)
    jax.block_until_ready(final)
    m = summarize(final, slo=slo if fspec is not None else None)
    m["wall_s"] = time.time() - t0
    m["decision_rounds"] = rounds
    m["decision_mean_s"] = m["wall_s"] / rounds   # whole-round proxy: the
    m["decision_p95_s"] = m["decision_mean_s"]    # jitted rollout does not
    m["decision_max_s"] = m["decision_mean_s"]    # isolate decode time
    m["scheduler_decision_s"] = m["decision_mean_s"]
    m["engine"] = "batched"
    return m


def _run_event_driven(backend: str, name: str, *, num_edges: int,
                      until: float, horizon: float, seed: int, batches: int,
                      slo: float = DEFAULT_SLO) -> dict:
    """One event-driven cell. On a fault scenario, the same materialized
    fail/recover/straggle timeline the batched cells fold into their
    arrival batch is scheduled into the heap, so the columns stay paired."""
    cc = _make_controller(backend, num_edges, batches, z_pad=256)
    cloud, cache = scenario_cloud_spec(name)
    sim = MultiEdgeSim(SimConfig(num_edges=num_edges, seed=seed,
                                 cloud=cloud, cache=cache), cc)
    interval = sim.cfg.round_interval
    fspec = scenario_fault_spec(name)
    if fspec is not None:
        rounds = max(1, int(round(until / interval)))
        ev = faults_lib.materialize_faults(fspec, num_edges, rounds,
                                          seed=seed)
        jit = None
        if fspec.jitter_sigma:
            # size the shared per-rid jitter table off the identical
            # arrival stream the batched cells materialize
            probe = materialize_rounds(scenario(name), num_edges, rounds,
                                       interval, seed=seed,
                                       max_per_round=256)
            n_rid = (int(probe["rid"].max()) + 1 if probe["mask"].any()
                     else 1)
            jit = faults_lib.jitter_table(fspec, n_rid, seed=seed)
        faults_lib.schedule_into_sim(sim, ev, interval, jit)
    t0 = time.time()
    m = sim.drive(scenario(name), until=until, run_until=horizon)
    m["wall_s"] = time.time() - t0
    if fspec is not None:
        resp = [r.finish_time - r.submit_time
                for e in sim.edges for r in e.completed]
        viol = sum(1 for r in resp if r > slo) \
            + (m["submitted"] - m["completed"])
        m["shed_requests"] = 0  # the event sim has no admission control
        m["shed_rate"] = 0.0
        m["slo"] = float(slo)
        m["slo_violation_frac"] = viol / max(m["submitted"], 1)
    return m


def run_sweep(scenarios: list[str], backends: list[str], *, num_edges: int = 5,
              until: float = 3.0, horizon: float = 400.0, seed: int = 0,
              batches: int = 800, slo: float = DEFAULT_SLO,
              verbose: bool = True) -> dict:
    for backend in backends:  # fail fast, before any cell is computed
        if backend.startswith("batched-"):
            inner = backend.split("-", 1)[1]
            if inner not in ASSIGN_FNS and inner not in POLICY_BACKENDS:
                _engine_assign_fn(inner, num_edges, batches)  # raises
    cells = {}
    winners = {}
    slo_winners = {}
    deadline_winners = {}
    for name in scenarios:
        cells[name] = {}
        fspec = scenario_fault_spec(name)
        for backend in backends:
            if backend.startswith("batched-"):
                m = _run_batched(backend, name, num_edges=num_edges,
                                 until=until, seed=seed, batches=batches,
                                 slo=slo)
            else:
                m = _run_event_driven(backend, name, num_edges=num_edges,
                                      until=until, horizon=horizon,
                                      seed=seed, batches=batches, slo=slo)
            # every cell — batched summarize/partials_to_summary and the
            # event sim's metrics() — now returns the full canonical
            # SUMMARY_KEYS schema, so the report indexes keys directly
            # instead of defaulting the ones an engine used to omit
            m["per_edge_completed"] = {str(k): v for k, v
                                       in m["per_edge_completed"].items()}
            cells[name][backend] = m
            if verbose:
                line = (f"  {name:20s} {backend:12s} completed="
                        f"{m['completed']:4d}/{m['submitted']:<4d} "
                        f"mean={m['mean_response']:7.3f} "
                        f"p95={m['p95_response']:7.3f} "
                        f"dec_mean={m['decision_mean_s'] * 1e3:6.2f}ms")
                if "slo_violation_frac" in m:
                    line += (f" shed={m['shed_rate']:5.3f} "
                             f"slo_viol={m['slo_violation_frac']:5.3f}")
                if m["deadline_total"]:
                    line += (f" dl_miss={m['deadline_miss_frac']:5.3f} "
                             f"cache_hit={m['cache_hit_rate']:5.3f} "
                             f"cloud={m['cloud_offload_frac']:5.3f}")
                print(line)
        # fault-free scenarios rank complete runs by mean response; fault
        # scenarios admit shed/dropped load, so rank everything that
        # completed work (and additionally by SLO-violation fraction)
        ok = {b: r for b, r in cells[name].items()
              if r["completed"] > 0
              and (fspec is not None or r["completed"] == r["submitted"])}
        if ok:
            winners[name] = min(ok, key=lambda b: ok[b]["mean_response"])
            if verbose:
                print(f"  {name:20s} -> best mean response: {winners[name]}")
        slo_ok = {b: r for b, r in ok.items() if "slo_violation_frac" in r}
        if slo_ok:
            slo_winners[name] = min(
                slo_ok, key=lambda b: (slo_ok[b]["slo_violation_frac"],
                                       slo_ok[b]["mean_response"]))
            if verbose:
                print(f"  {name:20s} -> best SLO violation:  "
                      f"{slo_winners[name]}")
        # deadline-carrying scenarios (cloud-*) additionally rank by
        # deadline-miss fraction — the edge-cloud counterpart of the SLO
        # column, ties broken by mean response
        dl_ok = {b: r for b, r in cells[name].items()
                 if r["completed"] > 0 and r["deadline_total"] > 0}
        if dl_ok:
            deadline_winners[name] = min(
                dl_ok, key=lambda b: (dl_ok[b]["deadline_miss_frac"],
                                      dl_ok[b]["mean_response"]))
            if verbose:
                print(f"  {name:20s} -> best deadline miss:  "
                      f"{deadline_winners[name]}")
    return {
        "schema": REPORT_SCHEMA,
        "config": {"num_edges": num_edges, "until": until,
                   "horizon": horizon, "seed": seed, "slo": slo,
                   "scenarios": scenarios, "backends": backends},
        "results": cells,
        "winners": winners,
        "slo_winners": slo_winners,
        "deadline_winners": deadline_winners,
    }


def main() -> None:
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenarios", default="all",
                    help="comma list, or 'all' for the full registry")
    ap.add_argument("--backends", default="greedy,local,random")
    ap.add_argument("--edges", type=int, default=5)
    ap.add_argument("--until", type=float, default=3.0,
                    help="arrival window (workload horizon)")
    ap.add_argument("--horizon", type=float, default=400.0,
                    help="simulation end time (lets late arrivals drain)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--batches", type=int, default=None,
                    help="training budget when a corais backend is requested "
                         "(default 800; the corais-admit head has its own "
                         "fixed budget, see benchmarks.common."
                         "get_resilient_policy)")
    ap.add_argument("--slo", type=float, default=DEFAULT_SLO,
                    help="response-time SLO for the fault-matrix columns")
    ap.add_argument("--chaos", action="store_true",
                    help="resilience fault matrix: default to the fault-"
                         "injected scenarios and the admission-policy / "
                         "dispatch-policy / greedy / local columns, writing "
                         "results/chaos_sweep.json")
    ap.add_argument("--out", default=None,
                    help="report path (default results/scenario_sweep.json; "
                         "results/chaos_sweep.json under --chaos)")
    args = ap.parse_args()

    if args.chaos:
        default_scenarios = [n for n in list_scenarios()
                             if scenario_fault_spec(n) is not None]
        default_backends = ("batched-corais-admit,batched-corais,"
                            "batched-greedy,batched-local")
        default_out, default_batches = "chaos_sweep.json", 800
    else:
        default_scenarios = list(list_scenarios())
        default_backends = None
        default_out, default_batches = "scenario_sweep.json", 800

    names = (default_scenarios if args.scenarios == "all"
             else args.scenarios.split(","))
    backends_arg = args.backends
    if args.chaos and backends_arg == ap.get_default("backends"):
        backends_arg = default_backends
    backends = backends_arg.split(",")
    batches = args.batches if args.batches is not None else default_batches
    print(f"== scenario sweep: {len(names)} scenarios x "
          f"{len(backends)} backends ==")
    report = run_sweep(names, backends, num_edges=args.edges,
                       until=args.until, horizon=args.horizon,
                       seed=args.seed, batches=batches, slo=args.slo)

    out = args.out or os.path.join(os.path.dirname(__file__), "..",
                                   "results", default_out)
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(out, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
    print(f"== report written to {os.path.abspath(out)} ==")


if __name__ == "__main__":
    main()
