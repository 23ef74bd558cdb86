"""Distributional equivalence of the device-resident episode sampler.

``materialize_round_batch_device`` draws with jax.random inside the trace,
so it can never be draw-for-draw identical to the host sampler — these
tests pin it to the same *laws* instead: count moments, size-distribution
KS statistics, edge/service/priority marginals, within-round time order
statistics, the overflow="clip" rid/dropped contract, and (slow) the
rollout-level cost a fixed policy sees on device vs host episodes.

KS thresholds are hand-rolled (no scipy in the container): the two-sample
acceptance band is c(alpha) * sqrt((n+m)/(n*m)) with c = 1.95 (alpha ~
1e-3), one-sample is c / sqrt(n)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.workloads import (DEADLINE_INF, Merged, MMPPArrivals,
                             PoissonArrivals, FlashCrowdArrivals, ServiceMix,
                             SizeSpec, edge_weights,
                             materialize_round_batch,
                             materialize_round_batch_device, scenario)

DT = 0.25


def device_batch(wl, num_edges, num_rounds, batch, width, seed=0):
    out = materialize_round_batch_device(
        wl, num_edges, num_rounds, DT, batch,
        key=jax.random.PRNGKey(seed), max_per_round=width)
    return {k: np.asarray(v) for k, v in out.items()}


def host_batch(wl, num_edges, num_rounds, batch, width, seed=0):
    return materialize_round_batch(
        wl, num_edges, num_rounds, DT, batch, base_seed=seed,
        max_per_round=width, overflow="clip")


def ks_two_sample(a, b):
    a, b = np.sort(a), np.sort(b)
    grid = np.concatenate([a, b])
    fa = np.searchsorted(a, grid, side="right") / a.size
    fb = np.searchsorted(b, grid, side="right") / b.size
    return float(np.max(np.abs(fa - fb)))


def ks_uniform(u):
    u = np.sort(u)
    n = u.size
    emp = np.arange(1, n + 1) / n
    return float(max(np.max(np.abs(emp - u)),
                     np.max(np.abs(emp - 1.0 / n - u))))


def test_poisson_count_moments():
    rate, R, B = 30.0, 8, 384
    d = device_batch(PoissonArrivals(rate=rate), 4, R, B, width=64)
    counts = d["mask"].sum(-1)          # (B, R)
    lam = rate * DT
    assert counts.mean() == pytest.approx(lam, rel=0.05)
    assert counts.var() == pytest.approx(lam, rel=0.15)
    assert d["dropped"].sum() == 0


def test_edge_marginal_matches_zipf_weights():
    Q = 5
    wl = PoissonArrivals(rate=40.0, edge_skew=1.5, hot_edge=1)
    d = device_batch(wl, Q, 8, 256, width=64)
    src = d["src"][d["mask"]]
    hist = np.bincount(src, minlength=Q) / src.size
    np.testing.assert_allclose(hist, edge_weights(Q, 1.5, 1), atol=0.02)


@pytest.mark.parametrize("spec", [
    SizeSpec("pareto", (1.5, 0.05)),
    SizeSpec("lognormal", (-1.5, 0.8)),
    SizeSpec("uniform", (0.2, 0.9)),
    SizeSpec("fixed", (0.37,)),
])
def test_size_law_matches_host(spec):
    d = device_batch(PoissonArrivals(rate=40.0, sizes=spec), 3, 8, 128,
                     width=64)
    dev = d["size"][d["mask"]].astype(np.float64)
    host = spec.sample(np.random.default_rng(7), dev.size)
    if spec.dist == "fixed":
        np.testing.assert_allclose(dev, 0.37, atol=1e-6)
        return
    dstat = ks_two_sample(dev, host)
    n, m = dev.size, host.size
    assert dstat < 1.95 * np.sqrt((n + m) / (n * m)), (spec, dstat)


def test_within_round_times_are_uniform_order_statistics():
    R = 6
    d = device_batch(PoissonArrivals(rate=30.0), 4, R, 256, width=64)
    t, mask = d["t"], d["mask"]
    rounds = np.arange(R)[None, :, None]
    lo, hi = rounds * DT, (rounds + 1) * DT
    assert np.all(t[mask] > (np.broadcast_to(lo, t.shape))[mask])
    assert np.all(t[mask] <= (np.broadcast_to(hi, t.shape))[mask] + 1e-6)
    # sorted within each round (masked prefix)
    diffs = np.diff(t, axis=-1)
    both = mask[..., 1:] & mask[..., :-1]
    assert np.all(diffs[both] >= 0)
    u = (t / DT - np.broadcast_to(rounds, t.shape))[mask]
    assert ks_uniform(np.clip(u, 0.0, 1.0)) < 1.95 / np.sqrt(u.size)


def test_clip_contract_rids_and_dropped():
    R, A, B = 6, 8, 64
    d = device_batch(PoissonArrivals(rate=120.0), 4, R, B, width=A)
    counts = d["mask"].sum(-1)                      # kept = min(n, A)
    assert (d["dropped"] > 0).any()
    assert np.all(counts[d["dropped"] > 0] == A)
    # clipped rounds keep the *earliest* A of n arrivals: the last kept one
    # sits at the A-th order statistic of n uniforms, Beta(A, n-A+1) * dt
    clipped = d["dropped"] > 0
    u_last = (d["t"][..., A - 1] / DT - np.arange(R))[clipped]
    n = (counts + d["dropped"])[clipped]
    expect = A / (n + 1.0)
    assert np.all((u_last > 0) & (u_last <= 1.0 + 1e-6))
    assert u_last.mean() == pytest.approx(expect.mean(), rel=0.05)
    # rids count *all* arrivals in time order: the gap between consecutive
    # rounds' ids equals the dropped tail of the earlier round
    for b in range(B):
        for r in range(R - 1):
            k = counts[b, r]
            if k == 0 or counts[b, r + 1] == 0:
                continue
            last_kept = d["rid"][b, r, k - 1]
            next_first = d["rid"][b, r + 1, 0]
            assert next_first - (last_kept + 1) == d["dropped"][b, r], (b, r)
    flat = d["rid"][d["mask"]]
    per_elem = d["mask"].reshape(B, -1)
    for b in range(B):
        ids = d["rid"].reshape(B, -1)[b][per_elem[b]]
        assert np.all(np.diff(ids) > 0)


def test_mmpp_round_profile_matches_host():
    # One MMPP episode's mean count has sd ~2.2 (a burst regime dominates
    # it), so the batch must be large for rel=0.1 to be a ~5-sigma band:
    # at B=2048 the sd of the device-host difference is ~0.07 on a mean
    # of ~3.2.
    wl = scenario("mmpp_bursty")
    R, B = 12, 2048
    d = device_batch(wl, 4, R, B, width=64)
    h = host_batch(wl, 4, R, B, width=64, seed=11)
    cd, ch = d["mask"].sum(-1), h["mask"].sum(-1)
    tol = 5.0 * np.sqrt(cd.var(0) / B + ch.var(0) / B) + 1e-9
    np.testing.assert_array_less(np.abs(cd.mean(0) - ch.mean(0)), tol)
    assert cd.mean() == pytest.approx(ch.mean(), rel=0.1)


def test_flash_crowd_spike_rounds_and_edge():
    wl = FlashCrowdArrivals(base_rate=10.0, multiplier=10.0,
                            spike_start=1.0, spike_duration=0.5,
                            spike_edge=2)
    R, Q, B = 8, 4, 256
    d = device_batch(wl, Q, R, B, width=64)
    counts = d["mask"].sum(-1).mean(0)              # per-round mean
    spike, base = counts[[4, 5]], counts[[0, 1, 2, 3, 6, 7]]
    assert spike.min() > 3.0 * base.max()
    in_spike = d["mask"][:, 4:6, :]
    frac_hot = (d["src"][:, 4:6, :][in_spike] == 2).mean()
    h = host_batch(wl, Q, R, B, width=64, seed=3)
    h_in = h["mask"][:, 4:6, :]
    h_hot = (h["src"][:, 4:6, :][h_in] == 2).mean()
    assert frac_hot == pytest.approx(h_hot, abs=0.05)


def test_service_mix_laws():
    wl = ServiceMix(PoissonArrivals(rate=40.0), num_services=6, skew=1.2,
                    deadline=(0.5, 2.0), deadline_frac=0.5,
                    priorities=(3.0, 1.0))
    d = device_batch(wl, 3, 8, 256, width=64)
    m = d["mask"]
    svc = d["service"][m]
    ranks = np.arange(6, dtype=np.float64)
    probs = (ranks + 1.0) ** -1.2
    probs /= probs.sum()
    np.testing.assert_allclose(np.bincount(svc, minlength=6) / svc.size,
                               probs, atol=0.02)
    prio = d["priority"][m]
    np.testing.assert_allclose(np.bincount(prio.astype(int), minlength=2)
                               / prio.size, [0.75, 0.25], atol=0.02)
    dl, t = d["deadline"][m], d["t"][m]
    finite = dl < DEADLINE_INF / 2
    assert finite.mean() == pytest.approx(0.5, abs=0.03)
    rel = (dl - t)[finite]
    assert np.all((rel >= 0.5 - 1e-5) & (rel <= 2.0 + 1e-5))
    u = np.clip((rel - 0.5) / 1.5, 0.0, 1.0)
    assert ks_uniform(u) < 1.95 / np.sqrt(u.size)


def test_unsupported_workloads_and_options_raise():
    mm = MMPPArrivals()
    with pytest.raises(ValueError, match="MMPP"):
        materialize_round_batch_device(Merged((mm, mm)), 3, 4, DT, 8,
                                       key=jax.random.PRNGKey(0),
                                       max_per_round=8)
    with pytest.raises(ValueError, match="clip"):
        materialize_round_batch_device(PoissonArrivals(), 3, 4, DT, 8,
                                       key=jax.random.PRNGKey(0),
                                       max_per_round=8, overflow="error")
    mixed = Merged((PoissonArrivals(sizes=SizeSpec("uniform")),
                    PoissonArrivals(sizes=SizeSpec("pareto", (1.5, 0.05)))))
    with pytest.raises(ValueError, match="[Ss]ize"):
        materialize_round_batch_device(mixed, 3, 4, DT, 8,
                                       key=jax.random.PRNGKey(0),
                                       max_per_round=8)


@pytest.mark.parametrize("name", ["uniform_iid", "hotspot_skew",
                                  "heavy_tail_pareto", "diurnal",
                                  "chaos-rolling-failure"])
def test_scenario_moment_parity_with_host(name):
    wl = scenario(name)
    R, Q, B = 8, 5, 192
    width = 64 if name != "chaos-rolling-failure" else 96
    d = device_batch(wl, Q, R, B, width=width)
    h = host_batch(wl, Q, R, B, width=width, seed=5)
    assert d["mask"].sum(-1).mean() == pytest.approx(
        h["mask"].sum(-1).mean(), rel=0.1)
    assert d["size"][d["mask"]].mean() == pytest.approx(
        h["size"][h["mask"]].mean(), rel=0.1)


@pytest.mark.slow
def test_rollout_cost_parity_device_vs_host():
    """A fixed (fresh) policy must see the same expected episode cost on
    device-sampled episodes as on host-sampled ones — the rollout-level
    check that the sampler feeds the engine the same workload law."""
    from repro.core import PolicyConfig
    from repro.core.policy import corais_init
    from repro.core.train import (TemporalRLConfig, _cluster_seeds,
                                  _element_keys, resolve_temporal_config,
                                  temporal_rl_loss)
    from repro.serving import engine as engine_lib
    from repro.serving.engine import EngineConfig

    B = 64
    cfg = TemporalRLConfig(
        policy=PolicyConfig(d_model=32, ff_hidden=64, edge_layers=1,
                            request_layers=1, norm="layer"),
        engine=EngineConfig(num_edges=4, num_rounds=6, max_per_round=16),
        scenario="uniform_iid", batch_size=B, seed=0)
    cfg, _ = resolve_temporal_config(cfg)
    ecfg = cfg.engine
    params, state = corais_init(jax.random.PRNGKey(0), cfg.policy)
    wl = scenario(cfg.scenario)

    @jax.jit
    def cost_of(sim0, arrivals, skeys):
        _, aux = temporal_rl_loss(params, state, sim0, arrivals, skeys, cfg)
        return aux["cost_mean"]

    key = jax.random.PRNGKey(cfg.seed)
    dev_costs, host_costs = [], []
    for b in range(3):
        sim0 = jax.tree.map(jnp.asarray,
                            engine_lib.init_batch(ecfg, _cluster_seeds(cfg, b)))
        skeys = _element_keys(key, b, B)
        ekeys = _element_keys(key, 100 + b, B)
        arr_keys = jax.vmap(lambda k: jax.random.fold_in(k, 1))(ekeys)
        dev = materialize_round_batch_device(
            wl, ecfg.num_edges, ecfg.num_rounds, ecfg.round_interval,
            keys=arr_keys, max_per_round=ecfg.max_per_round)
        host = jax.tree.map(jnp.asarray, materialize_round_batch(
            wl, ecfg.num_edges, ecfg.num_rounds, ecfg.round_interval, B,
            base_seed=1000 + b, max_per_round=ecfg.max_per_round,
            overflow="clip"))
        dev_costs.append(float(cost_of(sim0, dev, skeys)))
        host_costs.append(float(cost_of(sim0, host, skeys)))
    assert np.mean(dev_costs) == pytest.approx(np.mean(host_costs), rel=0.1)
