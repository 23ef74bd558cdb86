"""S-sample batch REINFORCE for CoRaiS (paper §IV-B, eqs 20-21).

One forward pass per instance yields the full factorized distribution;
S assignments are sampled from it, the shared-baseline advantage
A(pi_s) = L(pi_s) - mean_i L(pi_i) weights the log-prob gradient, and an
entropy bonus (eq 20) keeps exploration alive. Loss (eq 21):

    L(theta|D) = E_g[ C1 * sum_s log p(pi_s) A(pi_s) - C2 * H(g) ]

Paper hyperparameters: Adam lr 1e-5, batch 128 instances, S = 64,
C1 = 10, C2 = 0.5, uniform(-1/sqrt d) init.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import instances as inst_lib
from repro.core.decode import assignment_log_prob, greedy_decode
from repro.core.objective import makespan
from repro.core.policy import (PolicyConfig, corais_admit, corais_encode,
                               corais_init, corais_score)
from repro.optim import AdamConfig, adam_init, adam_update, clip_by_global_norm
from repro.platform import donate_default
from repro.resilience import faults as faults_lib
from repro.resilience.policies import nearest_alive
from repro.serving import engine as engine_lib
from repro.serving.engine import EngineConfig

# NOTE: repro.workloads is imported lazily inside temporal_train —
# workloads.scenarios depends on repro.serving (cloud/cache specs), which
# pulls in repro.core, so a module-level import here would be circular.


@dataclasses.dataclass(frozen=True)
class RLConfig:
    policy: PolicyConfig = PolicyConfig()
    instance: inst_lib.InstanceConfig = inst_lib.InstanceConfig()
    batch_size: int = 128
    num_samples: int = 64          # S
    c1: float = 10.0
    c2: float = 0.5
    lr: float = 1e-5
    grad_clip: float = 1.0
    num_batches: int = 40000
    seed: int = 0
    log_every: int = 10


def rl_loss(params, state, batch, sample_key, cfg: RLConfig):
    """Surrogate loss over a batch of instances. batch leaves have a leading
    batch axis; returns (loss, aux)."""
    # shared inference stack: one encode, one eq 16-17 score (the head's
    # backend — xla / ref / pallas — is cfg.policy.score_backend)
    c_emb, h_emb, new_state = corais_encode(
        params, state, batch, cfg.policy, training=True)
    log_probs = corais_score(params, c_emb, h_emb, batch["edge_mask"],
                             cfg.policy)  # (B, Z, Q)
    rmask = batch["req_mask"]

    # --- S samples from the factorized policy (no grad through sampling).
    # One batched categorical over a split-key axis: identical draws to the
    # per-key loop, but S-fold smaller jaxpr (the unrolled loop dominated
    # trace time at the paper's S=64).
    lp_stop = jax.lax.stop_gradient(log_probs)
    keys = jax.random.split(sample_key, cfg.num_samples)
    samples = jax.vmap(
        lambda k: jax.random.categorical(k, lp_stop, axis=-1)
    )(keys).astype(jnp.int32)  # (S, B, Z)

    costs = jax.vmap(lambda a: makespan(batch, a))(samples)  # (S, B)
    baseline = jnp.mean(costs, axis=0, keepdims=True)
    adv = costs - baseline  # (S, B)

    logp_pi = jax.vmap(lambda a: assignment_log_prob(log_probs, a, rmask))(samples)
    reinforce = jnp.sum(logp_pi * jax.lax.stop_gradient(adv), axis=0)  # (B,)

    # --- entropy (eq 20), over real (request, edge) cells
    probs = jnp.exp(log_probs)
    ent = -jnp.sum(probs * log_probs, axis=-1)  # (B, Z)
    ent = jnp.sum(ent * rmask, axis=-1)  # (B,)

    loss = jnp.mean(cfg.c1 * reinforce - cfg.c2 * ent)
    aux = {
        "cost_mean": jnp.mean(costs),
        "cost_best": jnp.mean(jnp.min(costs, axis=0)),
        "entropy": jnp.mean(ent),
        "state": new_state,
    }
    return loss, aux


def make_train_step(cfg: RLConfig, adam_cfg: Optional[AdamConfig] = None):
    adam_cfg = adam_cfg or AdamConfig(lr=cfg.lr)

    @jax.jit
    def step(params, state, opt_state, batch, key):
        (loss, aux), grads = jax.value_and_grad(rl_loss, has_aux=True)(
            params, state, batch, key, cfg
        )
        grads, gnorm = clip_by_global_norm(grads, cfg.grad_clip)
        params, opt_state = adam_update(params, grads, opt_state, adam_cfg)
        metrics = {
            "loss": loss,
            "grad_norm": gnorm,
            "cost_mean": aux["cost_mean"],
            "cost_best": aux["cost_best"],
            "entropy": aux["entropy"],
        }
        return params, aux["state"], opt_state, metrics

    return step, adam_cfg


def greedy_eval(params, state, batch, cfg: RLConfig) -> jax.Array:
    """Mean greedy makespan on a batch (no sampling)."""
    c_emb, h_emb, _ = corais_encode(params, state, batch, cfg.policy,
                                    training=False)
    log_probs = corais_score(params, c_emb, h_emb, batch["edge_mask"],
                             cfg.policy)
    return jnp.mean(makespan(batch, greedy_decode(log_probs)))


def train(
    cfg: RLConfig,
    num_batches: Optional[int] = None,
    params=None,
    state=None,
    opt_state=None,
    callback: Optional[Callable] = None,
    checkpointer=None,
    start_batch: int = 0,
):
    """Train CoRaiS on freshly generated synthetic instances (paper §IV-B).

    Returns (params, state, opt_state, history). Resumable: pass the pytrees
    back in (or use ``checkpointer`` for automatic periodic save/restore).
    """
    num_batches = num_batches if num_batches is not None else cfg.num_batches
    rng = np.random.default_rng(cfg.seed + 7919 * start_batch)
    key = jax.random.PRNGKey(cfg.seed)
    if params is None:
        key, sub = jax.random.split(key)
        params, state = corais_init(sub, cfg.policy)
    adam_cfg = AdamConfig(lr=cfg.lr)
    if opt_state is None:
        opt_state = adam_init(params, adam_cfg)
    step_fn, _ = make_train_step(cfg, adam_cfg)

    history = []
    for b in range(start_batch, start_batch + num_batches):
        batch = inst_lib.generate_batch(rng, cfg.instance, cfg.batch_size)
        batch = jax.tree.map(jnp.asarray, batch)
        key, sub = jax.random.split(key)
        t0 = time.perf_counter()
        params, state, opt_state, metrics = step_fn(params, state, opt_state, batch, sub)
        metrics = {k: float(v) for k, v in metrics.items()}
        metrics["batch"] = b
        metrics["sec"] = time.perf_counter() - t0
        history.append(metrics)
        if callback is not None and (b % cfg.log_every == 0):
            callback(metrics)
        if checkpointer is not None and checkpointer.should_save(b):
            checkpointer.save(
                b, {"params": params, "state": state, "opt_state": opt_state}
            )
    return params, state, opt_state, history


# ---------------------------------------------------------------------------
# Temporal REINFORCE on batched engine rollouts
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TemporalRLConfig:
    """REINFORCE over whole serving rollouts instead of i.i.d. static
    snapshots: the policy schedules every round of a scenario-conditioned
    episode inside :mod:`repro.serving.engine`, and the rollout return (mean
    response time over the episode's completed requests) replaces the
    single-round makespan as the learning signal — the temporal state the
    paper's §V-B3 perception claim is actually about."""

    policy: PolicyConfig = PolicyConfig()
    engine: EngineConfig = EngineConfig()
    scenario: str = "uniform_iid"   # repro.workloads scenario registry name
    batch_size: int = 16            # parallel rollouts (vmapped instances)
    c1: float = 1.0
    c2: float = 0.5
    lr: float = 1e-5
    grad_clip: float = 1.0
    num_batches: int = 1000
    seed: int = 0
    log_every: int = 10
    # Resilience training (the chaos-scenario path). Episodes are fault-
    # injected from the scenario's registered FaultSpec (or ``fault_spec``
    # here, which wins); ``admission=True`` samples the policy's admit head
    # per request and trains it jointly with dispatch. With ``slo > 0`` the
    # episode cost adds ``slo_penalty * slo_violation_frac``, where sheds,
    # drops, and stranded requests all count as violations — shedding
    # everything is never a winning strategy.
    fault_spec: Optional[faults_lib.FaultSpec] = None
    admission: bool = False
    slo: float = 0.0
    slo_penalty: float = 0.0
    # Deadline-aware training (schema v3): with ``deadline_penalty > 0``
    # the episode cost adds ``deadline_penalty * deadline_miss_frac`` —
    # the fraction of committed finite-deadline requests that finished
    # past their deadline (or never finished). Pairs with
    # ``policy.tier_features`` so the encoder can see the slack it is
    # being charged for.
    deadline_penalty: float = 0.0
    # Train only the admission head, freezing every other parameter (the
    # warm-started dispatch weights): episode-level REINFORCE at small
    # batch sizes is noisy enough to destroy a good dispatch policy, and
    # the admission decision is learnable on its own on top of it.
    freeze_dispatch: bool = False
    # Device-resident training. With ``device_episodes=True`` arrivals (and
    # fault tensors) are drawn *inside* jit with jax.random
    # (workloads.materialize_round_batch_device), so episode generation
    # never round-trips through host numpy; ``epoch_len`` K > 1 runs K
    # REINFORCE updates per dispatch under one lax.scan with donated
    # params/opt_state buffers. Either setting (or passing ``mesh=`` to
    # temporal_train) routes through the scanned epoch trainer; only
    # scenarios with a device sampling law are supported there.
    device_episodes: bool = False
    epoch_len: int = 1


def temporal_rl_loss(params, policy_state, sim_state, arrivals, sample_key,
                     cfg: TemporalRLConfig, axis_name: Optional[str] = None):
    """Surrogate loss over a batch of rollouts. ``sim_state`` is a (B,)-
    batched engine state, ``arrivals`` (B, R, A) padded round batches.
    Actions are sampled per round from the factorized policy; the episode
    return is the mean response time over completed requests, with the
    batch-mean baseline. Returns (loss, aux).

    ``sample_key`` is either one (2,) key (batch-wide draws) or a (B, 2)
    per-element key stack — per-element draws are what make the data-
    parallel trainer exactly equivalent to single-device training, since an
    element's actions then never depend on how the batch is sharded. With
    ``axis_name`` set (inside shard_map) the REINFORCE baseline and the
    reported aux metrics reduce over the global batch via pmean/pmin; the
    loss itself stays shard-local (the train step pmean-averages grads)."""
    ecfg = cfg.engine
    fault_mode = "alive" in arrivals
    per_elem = sample_key.ndim == 2
    if axis_name is None:
        gmean, gmin = jnp.mean, jnp.min
    else:
        gmean = lambda x: jax.lax.pmean(jnp.mean(x), axis_name)  # noqa: E731
        gmin = lambda x: jax.lax.pmin(jnp.min(x), axis_name)     # noqa: E731
    adv_fn = jax.vmap(
        lambda st: engine_lib.advance(st, st["t"] + ecfg.round_interval, ecfg))
    inst_fn = jax.vmap(lambda st, a: engine_lib.round_instance(st, a, ecfg))
    commit_fn = jax.vmap(
        lambda st, a, x, adm, ro: engine_lib.commit(st, a, x, ecfg, admit=adm,
                                                    ready_offset=ro))
    fault_fn = jax.vmap(lambda st, a: engine_lib.apply_faults(st, a, ecfg))
    remap_fn = jax.vmap(
        lambda st, s: nearest_alive(st["w"], st["alive"] > 0, s))
    drain_fn = jax.vmap(
        lambda st: engine_lib.advance(st, engine_lib.DRAIN_HORIZON, ecfg))

    def body(carry, arr):
        sim, key = carry
        if per_elem:
            ks = jax.vmap(lambda k: jax.random.split(k, 3))(key)  # (B, 3, 2)
            key, sub, sub_adm = ks[:, 0], ks[:, 1], ks[:, 2]
        else:
            key, sub, sub_adm = jax.random.split(key, 3)
        sim = adv_fn(sim)
        ready_offset = jnp.zeros_like(arr["size"])
        if fault_mode:
            # the engine's two-step admission failover (see step_round):
            # arrivals re-admitted by the second step sort after native ones
            arr = dict(arr)
            arr["src"] = remap_fn(
                sim, jnp.clip(arr["src"].astype(jnp.int32), 0,
                              ecfg.num_edges - 1))
            sim = fault_fn(sim, arr)
            readmitted = ~jnp.take_along_axis(
                sim["alive"] > 0, arr["src"], axis=-1)
            ready_offset = engine_lib.RETRY_EPS * readmitted
            arr["src"] = remap_fn(sim, arr["src"])
        inst = inst_fn(sim, arr)
        # eval-mode norm statistics: rounds of one rollout are far from
        # i.i.d., so running batchnorm stats are not updated here.
        c_emb, h_emb, _ = corais_encode(params, policy_state, inst,
                                        cfg.policy, training=False)
        log_probs = corais_score(params, c_emb, h_emb, inst["edge_mask"],
                                 cfg.policy)  # (B, A, Q)
        lp_stop = jax.lax.stop_gradient(log_probs)
        if per_elem:
            act = jax.vmap(
                lambda k, lp: jax.random.categorical(k, lp, axis=-1)
            )(sub, lp_stop).astype(jnp.int32)
        else:
            act = jax.random.categorical(sub, lp_stop,
                                         axis=-1).astype(jnp.int32)
        rmask = inst["req_mask"]
        probs = jnp.exp(log_probs)
        ent = jnp.sum(-jnp.sum(probs * log_probs, -1) * rmask, -1)
        if cfg.admission:
            logits = corais_admit(params, c_emb, h_emb, inst["edge_mask"],
                                  cfg.policy)  # (B, A)
            sig = jax.nn.sigmoid(jax.lax.stop_gradient(logits))
            admit = (jax.vmap(jax.random.bernoulli)(sub_adm, sig)
                     if per_elem else jax.random.bernoulli(sub_adm, sig))
            logp_admit = jnp.sum(
                jnp.where(rmask,
                          jnp.where(admit, jax.nn.log_sigmoid(logits),
                                    jax.nn.log_sigmoid(-logits)), 0.0), -1)
            # a shed request's dispatch never executes: drop it from the
            # dispatch log-prob to cut gradient variance (still unbiased)
            logp = (assignment_log_prob(log_probs, act, rmask & admit)
                    + logp_admit)
        else:
            admit = jnp.ones_like(rmask)
            logp = assignment_log_prob(log_probs, act, rmask)  # (B,)
        sim = commit_fn(sim, arr, act, admit, ready_offset)
        return (sim, key), (logp, ent)

    arr_rb = jax.tree.map(lambda x: jnp.swapaxes(x, 0, 1), arrivals)
    (sim, _), (logps, ents) = jax.lax.scan(body, (sim_state, sample_key),
                                           arr_rb)
    sim = drain_fn(sim)

    committed = sim["slot_edge"] >= 0                       # (B, Z)
    # a fault trajectory can strand slots on a dead-at-horizon edge with
    # finish == INF; mean response is over realized completions only
    done = committed & (sim["slot_finish"] < engine_lib.INF / 2)
    resp = jnp.where(done, sim["slot_finish"] - sim["slot_submit"], 0.0)
    n_done = jnp.maximum(jnp.sum(done, -1), 1)
    cost = jnp.sum(resp, -1) / n_done                       # (B,) mean response
    aux = {}
    if cfg.slo > 0:
        violations = (jnp.sum(done & (resp > cfg.slo), -1)
                      + jnp.sum(committed & ~done, -1)
                      + sim["shed"] + sim["dropped"])
        total = jnp.maximum(
            jnp.sum(committed, -1) + sim["shed"] + sim["dropped"], 1)
        viol_frac = violations.astype(jnp.float32) / total
        cost = cost + cfg.slo_penalty * viol_frac
        aux["slo_violation_frac"] = gmean(viol_frac)
    if cfg.deadline_penalty > 0:
        finite = committed & (sim["slot_deadline"] < engine_lib.INF / 2)
        missed = finite & (~done
                           | (sim["slot_finish"] > sim["slot_deadline"]))
        miss_frac = (jnp.sum(missed, -1).astype(jnp.float32)
                     / jnp.maximum(jnp.sum(finite, -1), 1))
        cost = cost + cfg.deadline_penalty * miss_frac
        aux["deadline_miss_frac"] = gmean(miss_frac)
    # global-batch baseline: under shard_map every shard subtracts the same
    # mean, so pmean-averaged grads equal the single-device grads exactly
    adv = cost - gmean(cost)

    reinforce = jnp.sum(logps, axis=0) * jax.lax.stop_gradient(adv)  # (B,)
    ent_sum = jnp.sum(ents, axis=0)                                  # (B,)
    # loss is shard-local (adv is stop-gradiented, so no autodiff crosses
    # the collective); the train step pmean-averages grads
    loss = jnp.mean(cfg.c1 * reinforce) - cfg.c2 * jnp.mean(ent_sum)
    aux.update({
        "cost_mean": gmean(cost),
        "cost_best": gmin(cost),
        "entropy": gmean(ent_sum),
        "completed": gmean(jnp.sum(done, -1).astype(jnp.float32)),
        "shed": gmean(sim["shed"].astype(jnp.float32)),
    })
    return loss, aux


def _temporal_update(params, policy_state, opt_state, sim_state, arrivals,
                     sample_key, cfg: TemporalRLConfig, adam_cfg: AdamConfig,
                     axis_name: Optional[str] = None):
    """One REINFORCE update (loss → grads → clip → adam). Shared by the
    per-batch jitted step, the scanned epoch step, and the sharded trainer
    (``axis_name`` set: grads/loss pmean over the batch shards)."""
    (loss, aux), grads = jax.value_and_grad(temporal_rl_loss, has_aux=True)(
        params, policy_state, sim_state, arrivals, sample_key, cfg, axis_name
    )
    if cfg.freeze_dispatch:
        if cfg.admission and "admit" in grads:
            grads = {k: (g if k == "admit"
                         else jax.tree.map(jnp.zeros_like, g))
                     for k, g in grads.items()}
        else:
            raise ValueError(
                "freeze_dispatch requires admission=True and a policy "
                "with admit_head=True (nothing would train otherwise)")
    if axis_name is not None:
        grads = jax.lax.pmean(grads, axis_name)
        loss = jax.lax.pmean(loss, axis_name)
    grads, gnorm = clip_by_global_norm(grads, cfg.grad_clip)
    params, opt_state = adam_update(params, grads, opt_state, adam_cfg)
    metrics = {"loss": loss, "grad_norm": gnorm, **aux}
    return params, opt_state, metrics


def make_temporal_train_step(cfg: TemporalRLConfig,
                             adam_cfg: Optional[AdamConfig] = None):
    adam_cfg = adam_cfg or AdamConfig(lr=cfg.lr)

    @jax.jit
    def step(params, policy_state, opt_state, sim_state, arrivals, key):
        return _temporal_update(params, policy_state, opt_state, sim_state,
                                arrivals, key, cfg, adam_cfg)

    return step, adam_cfg


def resolve_temporal_config(cfg: TemporalRLConfig):
    """Thread the scenario's registered CloudSpec/CacheSpec into the engine
    config and resolve the effective fault spec (``cfg.fault_spec`` wins
    over the registry; spec with no faults drops to None). Idempotent —
    both trainer entry points and the benchmarks share it."""
    from repro.workloads.scenarios import (scenario_cloud_spec,
                                           scenario_fault_spec)

    ecfg = cfg.engine
    cloud_spec, cache_spec = scenario_cloud_spec(cfg.scenario)
    if cloud_spec is not None and ecfg.cloud is None:
        # cloud-* scenarios pin their tier + cache laws in the registry;
        # thread them into the engine automatically (like fault specs)
        ecfg = dataclasses.replace(ecfg, cloud=cloud_spec, cache=cache_spec)
        cfg = dataclasses.replace(cfg, engine=ecfg)
    fspec = cfg.fault_spec
    if fspec is None:
        fspec = scenario_fault_spec(cfg.scenario)
    if fspec is not None and not fspec.has_faults:
        fspec = None
    return cfg, fspec


def make_temporal_epoch_step(cfg: TemporalRLConfig,
                             adam_cfg: Optional[AdamConfig] = None, *,
                             mesh=None, axis: str = "fleet",
                             donate: Optional[bool] = None):
    """Scanned multi-update epoch step: one jit dispatch runs K sequential
    REINFORCE updates with episodes — arrivals and fault tensors — drawn
    *inside* the trace by the device samplers, so the host only supplies
    cluster states and PRNG keys.

    The returned ``step(params, policy_state, opt_state, sim0, elem_keys)``
    takes a (K, B, ...) stack of initial engine states and (K, B, 2)
    per-element keys (episode randomness derives from each element's key:
    fold_in 1 → arrivals, 2 → action sampling, 3 → faults), and returns
    ``(params, opt_state, metrics)`` with every metric stacked (K,) on
    device — nothing blocks until the caller drains them.

    With ``mesh`` the batch axis is sharded over the 1-D ``(axis,)`` device
    mesh (``launch.make_fleet_mesh``) under shard_map: params/opt_state are
    replicated, grads pmean-averaged, and per-element keys make the result
    equivalent to single-device training (pinned at 1e-5 by
    tests/test_train_multidevice.py). ``donate`` donates params/opt_state
    buffers to the dispatch; the default enables it off-CPU only (CPU jax
    warns and copies on donation — same contract as serving.fastpath).
    """
    from repro.workloads import materialize_round_batch_device, scenario
    from repro.workloads.batch import compile_device_plan

    adam_cfg = adam_cfg or AdamConfig(lr=cfg.lr)
    cfg, fspec = resolve_temporal_config(cfg)
    ecfg = cfg.engine
    wl = scenario(cfg.scenario)
    # fail fast (and outside jit) on scenarios with no device sampling law
    compile_device_plan(wl, ecfg.num_edges, ecfg.num_rounds,
                        ecfg.round_interval)
    if donate is None:
        donate = donate_default()
    axis_name = axis if mesh is not None else None

    def epoch(params, policy_state, opt_state, sim0, elem_keys):
        def one_update(carry, xs):
            params, opt_state = carry
            sim, ekeys = xs
            arr_keys = jax.vmap(lambda k: jax.random.fold_in(k, 1))(ekeys)
            arrivals = materialize_round_batch_device(
                wl, ecfg.num_edges, ecfg.num_rounds, ecfg.round_interval,
                keys=arr_keys, max_per_round=ecfg.max_per_round)
            if fspec is not None:
                fkeys = jax.vmap(lambda k: jax.random.fold_in(k, 3))(ekeys)
                arrivals = faults_lib.attach_fault_batch_device(
                    arrivals, fspec, ecfg.num_edges, fkeys)
            skeys = jax.vmap(lambda k: jax.random.fold_in(k, 2))(ekeys)
            params, opt_state, metrics = _temporal_update(
                params, policy_state, opt_state, sim, arrivals, skeys,
                cfg, adam_cfg, axis_name=axis_name)
            return (params, opt_state), metrics

        (params, opt_state), metrics = jax.lax.scan(
            one_update, (params, opt_state), (sim0, elem_keys))
        return params, opt_state, metrics

    donate_args = (0, 2) if donate else ()
    if mesh is None:
        return jax.jit(epoch, donate_argnums=donate_args), adam_cfg

    from jax.sharding import PartitionSpec

    cache: dict = {}

    def step(params, policy_state, opt_state, sim0, elem_keys):
        sig = jax.tree.structure(sim0)
        fn = cache.get(sig)
        if fn is None:
            batched = lambda x: PartitionSpec(  # noqa: E731
                None, axis, *(None,) * (x.ndim - 2))
            fn = jax.jit(
                jax.shard_map(
                    epoch, mesh=mesh,
                    in_specs=(PartitionSpec(), PartitionSpec(),
                              PartitionSpec(), jax.tree.map(batched, sim0),
                              PartitionSpec(None, axis, None)),
                    out_specs=(PartitionSpec(), PartitionSpec(),
                               PartitionSpec()),
                    check_vma=False),
                donate_argnums=donate_args)
            cache[sig] = fn
        return fn(params, policy_state, opt_state, sim0, elem_keys)

    return step, adam_cfg


#: rng-stream salts deriving per-batch episode randomness from
#: (cfg.seed, batch index) — order-free, so a checkpoint resume at any
#: batch replays exactly the stream an uninterrupted run would consume.
_CLUSTER_SALT = 0xC1
_ARRIVAL_SALT = 0xA7
_FAULT_SEED_SALT = 0xFA


def _cluster_seeds(cfg: TemporalRLConfig, b: int) -> np.ndarray:
    return np.random.default_rng((cfg.seed, _CLUSTER_SALT, b)).integers(
        0, 2**31 - 1, size=cfg.batch_size)


def _element_keys(base_key, b: int, batch: int):
    """(B, 2) per-element PRNG keys for batch index ``b``."""
    kb = jax.random.fold_in(base_key, b)
    return jax.vmap(lambda i: jax.random.fold_in(kb, i))(
        jnp.arange(batch, dtype=jnp.uint32))


def temporal_train(
    cfg: TemporalRLConfig,
    num_batches: Optional[int] = None,
    params=None,
    state=None,
    opt_state=None,
    callback: Optional[Callable] = None,
    *,
    mesh=None,
    checkpointer=None,
    start_batch: int = 0,
    adam_cfg: Optional[AdamConfig] = None,
):
    """Train CoRaiS on temporal rollouts of a registered workload scenario.

    Every batch samples ``batch_size`` fresh clusters and arrival episodes
    (scenario-conditioned), rolls all of them forward in parallel on device,
    and applies one REINFORCE update on the episode returns. Returns
    (params, state, opt_state, history) like :func:`train`.

    Two execution paths share one update rule (:func:`_temporal_update`):

    * host loop (default: ``device_episodes=False``, ``epoch_len<=1``, no
      mesh) — one jitted step per batch on host-materialized episodes;
      metrics stay device arrays in-loop and drain every ``log_every``.
    * scanned epoch (``device_episodes=True`` or ``epoch_len>1`` or
      ``mesh=``) — :func:`make_temporal_epoch_step`: K updates per
      dispatch, in-jit episode generation, optional batch sharding over
      the fleet mesh. ``callback`` then fires once per drained epoch (with
      that epoch's last batch row), not per batch.

    Per-batch randomness (clusters, arrivals, faults, action sampling)
    derives from ``(cfg.seed, batch index)`` rather than a sequentially
    consumed stream, so resuming from a ``checkpointer`` snapshot at any
    batch replays exactly what the uninterrupted run would have drawn —
    save→resume is bit-identical. With ``checkpointer`` set, parameters
    auto-restore from its latest snapshot (saved under step = number of
    completed batches) unless explicit ``params`` are passed."""
    from repro.workloads import materialize_round_batch, scenario

    cfg, fspec = resolve_temporal_config(cfg)
    num_batches = num_batches if num_batches is not None else cfg.num_batches
    ecfg = cfg.engine
    wl = scenario(cfg.scenario)
    key = jax.random.PRNGKey(cfg.seed)
    adam_cfg = adam_cfg or AdamConfig(lr=cfg.lr)
    if checkpointer is not None and params is None:
        template = jax.eval_shape(
            lambda: corais_init(jax.random.split(key)[1], cfg.policy))
        opt_template = jax.eval_shape(
            lambda: adam_init(template[0], adam_cfg))
        restored = checkpointer.restore_latest(
            {"params": template[0], "state": template[1],
             "opt_state": opt_template})
        if restored is not None:
            params = restored["tree"]["params"]
            state = restored["tree"]["state"]
            opt_state = restored["tree"]["opt_state"]
            start_batch = int(restored["step"])
    if params is None:
        params, state = corais_init(jax.random.split(key)[1], cfg.policy)
    if opt_state is None:
        opt_state = adam_init(params, adam_cfg)

    use_epoch = (cfg.device_episodes or cfg.epoch_len > 1
                 or mesh is not None)
    end = start_batch + num_batches
    history: list = []
    pending: list = []  # (batch ids, sec per batch, device metrics)

    def drain():
        rows = []
        for bs, sec, mets in pending:
            host = jax.device_get(mets)
            for i, b_i in enumerate(bs):
                row = {k: float(v[i]) if np.ndim(v) else float(v)
                       for k, v in host.items()}
                row["batch"], row["sec"] = b_i, sec
                history.append(row)
                rows.append(row)
        pending.clear()
        return rows

    def save(step_idx):
        if checkpointer is not None and checkpointer.should_save(step_idx):
            checkpointer.save(step_idx, {"params": params, "state": state,
                                         "opt_state": opt_state})
            return True
        return False

    if not use_epoch:
        step_fn, _ = make_temporal_train_step(cfg, adam_cfg)
        for b in range(start_batch, end):
            sim0 = engine_lib.init_batch(ecfg, _cluster_seeds(cfg, b))
            # overflow="clip": a burst beyond max_per_round drops its tail
            # in *training* episodes (a bounded admission queue), never in
            # evals.
            arrivals = materialize_round_batch(
                wl, ecfg.num_edges, ecfg.num_rounds, ecfg.round_interval,
                cfg.batch_size,
                base_seed=int(np.random.default_rng(
                    (cfg.seed, _ARRIVAL_SALT, b)).integers(0, 2**31 - 1)),
                max_per_round=ecfg.max_per_round, overflow="clip")
            if fspec is not None:
                arrivals = faults_lib.attach_fault_batch(
                    arrivals, fspec, ecfg.num_edges,
                    seeds=np.random.default_rng(
                        (cfg.seed, _FAULT_SEED_SALT, b)).integers(
                            0, 2**31 - 1, size=cfg.batch_size))
            skeys = _element_keys(key, b, cfg.batch_size)
            t0 = time.perf_counter()
            params, opt_state, metrics = step_fn(
                params, state, opt_state,
                jax.tree.map(jnp.asarray, sim0),
                jax.tree.map(jnp.asarray, arrivals), skeys)
            pending.append(([b], time.perf_counter() - t0, metrics))
            # metrics stay on device between drains: no per-batch sync
            if b % cfg.log_every == 0 or b == end - 1:
                rows = drain()
                if callback is not None and rows and b % cfg.log_every == 0:
                    callback(rows[-1])
            save(b + 1)
        drain()
        return params, state, opt_state, history

    if mesh is not None:
        shards = int(np.prod([d for d in mesh.devices.shape]))
        if cfg.batch_size % shards:
            raise ValueError(
                f"batch_size {cfg.batch_size} does not divide over the "
                f"{shards}-device mesh")
    step_fn, _ = make_temporal_epoch_step(cfg, adam_cfg, mesh=mesh)
    epoch_len = max(1, cfg.epoch_len)
    b = start_batch
    while b < end:
        k_len = min(epoch_len, end - b)
        if checkpointer is not None:
            # land chunk boundaries exactly on checkpoint steps so a resume
            # replays the same chunking (bit-identical histories)
            k_len = min(k_len,
                        checkpointer.every - b % checkpointer.every)
        bs = list(range(b, b + k_len))
        stacks = [engine_lib.init_batch(ecfg, _cluster_seeds(cfg, bi))
                  for bi in bs]
        sim0 = {k: jnp.asarray(np.stack([s[k] for s in stacks]))
                for k in stacks[0]}
        ekeys = jnp.stack([_element_keys(key, bi, cfg.batch_size)
                           for bi in bs])
        t0 = time.perf_counter()
        params, opt_state, mets = step_fn(params, state, opt_state, sim0,
                                          ekeys)
        pending.append((bs, (time.perf_counter() - t0) / k_len, mets))
        b += k_len
        n_pending = sum(len(p[0]) for p in pending)
        if callback is not None or n_pending >= cfg.log_every or b >= end:
            rows = drain()
            if callback is not None and rows:
                callback(rows[-1])  # per-epoch logging
        save(b)
    drain()
    return params, state, opt_state, history
