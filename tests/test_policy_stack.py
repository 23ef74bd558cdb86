"""Unified policy inference stack: encode/score split, backend registry
parity (xla / ref / pallas-interpret), fused-decode parity and
no-materialization guarantees, custom-VJP gradients, mask invariance under
padding, and the engine's named policy backends."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import InstanceConfig, generate_batch
from repro.core.inference import make_decision_fn, policy_decide
from repro.core.policy import (PolicyConfig, corais_apply, corais_encode,
                               corais_init, corais_score,
                               corais_score_decode, list_score_backends)
from repro.serving import engine
from repro.workloads import materialize_rounds, scenario

CFG = PolicyConfig(d_model=32, ff_hidden=64, edge_layers=2, request_layers=1)
BACKENDS = ("xla", "ref", "pallas")


def _batch(seed=0, b=3, q=5, z=12, q_pad=None, z_pad=None):
    rng = np.random.default_rng(seed)
    batch = generate_batch(
        rng,
        InstanceConfig(num_edges=q, num_requests=z, max_edges=q_pad,
                       max_requests=z_pad),
        b)
    return jax.tree.map(jnp.asarray, batch)


# -- encode/score split ------------------------------------------------------


def test_encode_score_composition_is_apply():
    params, state = corais_init(jax.random.PRNGKey(0), CFG)
    batch = _batch()
    lp_apply, st_apply = corais_apply(params, state, batch, CFG, training=True)
    c, h, st_split = corais_encode(params, state, batch, CFG, training=True)
    lp_split = corais_score(params, c, h, batch["edge_mask"], CFG)
    np.testing.assert_array_equal(np.asarray(lp_apply), np.asarray(lp_split))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), st_apply, st_split)


def test_registry_lists_all_backends_and_rejects_unknown():
    assert set(BACKENDS) <= set(list_score_backends())
    params, state = corais_init(jax.random.PRNGKey(0), CFG)
    batch = _batch(b=1)
    c, h, _ = corais_encode(params, state, batch, CFG)
    with pytest.raises(ValueError, match="unknown score backend"):
        corais_score(params, c, h, batch["edge_mask"], CFG, backend="nope")


# -- kernel parity (satellite: pallas-interpret vs ref vs xla <= 1e-5) -------


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_score_backend_parity_with_xla_head(backend):
    """Same encoder outputs through every head implementation: log-probs
    agree to <= 1e-5, batched and unbatched, mask included."""
    params, state = corais_init(jax.random.PRNGKey(0), CFG)
    batch = _batch(q=4, q_pad=6, z=9, z_pad=13)  # padded + odd Z
    c, h, _ = corais_encode(params, state, batch, CFG)
    lp_xla = corais_score(params, c, h, batch["edge_mask"], CFG, backend="xla")
    lp = corais_score(params, c, h, batch["edge_mask"], CFG, backend=backend)
    np.testing.assert_allclose(np.asarray(lp), np.asarray(lp_xla),
                               rtol=1e-5, atol=1e-5)
    # unbatched single instance through the same entry (same embeddings,
    # different backend — untrained batchnorm stats depend on batch width,
    # so the xla reference is recomputed on the unbatched encoder outputs)
    inst = jax.tree.map(lambda x: x[0], batch)
    c1, h1, _ = corais_encode(params, state, inst, CFG)
    lp1 = corais_score(params, c1, h1, inst["edge_mask"], CFG, backend=backend)
    lp1_xla = corais_score(params, c1, h1, inst["edge_mask"], CFG,
                           backend="xla")
    np.testing.assert_allclose(np.asarray(lp1), np.asarray(lp1_xla),
                               rtol=1e-5, atol=1e-5)


def test_apply_backend_kwarg_end_to_end_parity():
    params, state = corais_init(jax.random.PRNGKey(1), CFG)
    batch = _batch(seed=5)
    lps = {b: corais_apply(params, state, batch, CFG, backend=b)[0]
           for b in BACKENDS}
    for b in ("ref", "pallas"):
        np.testing.assert_allclose(np.asarray(lps[b]), np.asarray(lps["xla"]),
                                   rtol=1e-5, atol=1e-5)


# -- custom VJP (satellite: finite-difference gradient check) ----------------


def test_pallas_vjp_matches_finite_differences():
    """Central finite differences on the fused kernel's scalar loss vs the
    custom_vjp gradients, for every differentiable input."""
    from repro.kernels import ops
    q, z, d = 4, 7, 8
    c = jax.random.normal(jax.random.PRNGKey(0), (q, d))
    h = jax.random.normal(jax.random.PRNGKey(1), (z, d))
    wx = jax.random.normal(jax.random.PRNGKey(2), (d, d)) * 0.2
    wy = jax.random.normal(jax.random.PRNGKey(3), (d, d)) * 0.2
    mask = jnp.asarray([True, True, True, False])
    w = jax.random.normal(jax.random.PRNGKey(4), (z, q))

    def loss(c, h, wx, wy):
        lp = ops.policy_score(c, h, wx, wy, mask, bz=4)
        return jnp.sum(jnp.exp(lp) * w)  # bounded in every direction

    args = (c, h, wx, wy)
    grads = jax.grad(loss, argnums=(0, 1, 2, 3))(*args)
    eps = 1e-3
    rng = np.random.default_rng(0)
    for ai, g in enumerate(grads):
        g = np.asarray(g)
        for _ in range(5):  # spot-check coordinates
            idx = tuple(rng.integers(0, s) for s in g.shape)
            e = np.zeros(g.shape, np.float32)
            e[idx] = eps
            hi = list(args)
            lo = list(args)
            hi[ai] = args[ai] + e
            lo[ai] = args[ai] - e
            fd = (float(loss(*hi)) - float(loss(*lo))) / (2 * eps)
            np.testing.assert_allclose(g[idx], fd, rtol=5e-2, atol=5e-3,
                                       err_msg=f"arg {ai} coord {idx}")


def test_pallas_grads_match_xla_backend_grads():
    """grad through corais_score must agree across backends (REINFORCE
    trains through whichever head is configured)."""
    params, state = corais_init(jax.random.PRNGKey(0), CFG)
    batch = _batch(b=2, z=9)
    c, h, _ = corais_encode(params, state, batch, CFG)
    w = jax.random.normal(jax.random.PRNGKey(9), (2, 9, 5))

    def loss(c, h, backend):
        lp = corais_score(params, c, h, batch["edge_mask"], CFG,
                          backend=backend)
        return jnp.sum(jnp.exp(lp) * w)

    for backend in ("ref", "pallas"):
        gc, gh = jax.grad(lambda a, b: loss(a, b, backend), (0, 1))(c, h)
        gc0, gh0 = jax.grad(lambda a, b: loss(a, b, "xla"), (0, 1))(c, h)
        np.testing.assert_allclose(np.asarray(gc), np.asarray(gc0),
                                   rtol=1e-4, atol=1e-5, err_msg=backend)
        np.testing.assert_allclose(np.asarray(gh), np.asarray(gh0),
                                   rtol=1e-4, atol=1e-5, err_msg=backend)


def test_pallas_backend_under_vmap_and_grad():
    """The fused kernel inside vmap (the engine's instance axis) and grad
    through that vmap (temporal REINFORCE) both match the xla head."""
    params, state = corais_init(jax.random.PRNGKey(0), CFG)
    batch = _batch(b=3)
    w = jax.random.normal(jax.random.PRNGKey(7), (3, 12, 5))

    def one(inst, backend):
        c, h, _ = corais_encode(params, state, inst, CFG)
        return corais_score(params, c, h, inst["edge_mask"], CFG,
                            backend=backend)

    lp_p = jax.vmap(lambda i: one(i, "pallas"))(batch)
    lp_x = jax.vmap(lambda i: one(i, "xla"))(batch)
    np.testing.assert_allclose(np.asarray(lp_p), np.asarray(lp_x),
                               rtol=1e-5, atol=1e-5)

    def loss(params, backend):
        return jnp.sum(jnp.exp(jax.vmap(
            lambda i: one_p(params, i, backend))(batch)) * w)

    def one_p(params, inst, backend):
        c, h, _ = corais_encode(params, state, inst, CFG)
        return corais_score(params, c, h, inst["edge_mask"], CFG,
                            backend=backend)

    from jax.flatten_util import ravel_pytree
    gp = jax.grad(loss)(params, "pallas")
    gx = jax.grad(loss)(params, "xla")
    flat_p, _ = ravel_pytree(gp)
    flat_x, _ = ravel_pytree(gx)
    np.testing.assert_allclose(np.asarray(flat_p), np.asarray(flat_x),
                               rtol=1e-4, atol=1e-5)


# -- mask invariance (satellite: padding must not leak) ----------------------


_PAD_EDGE_KEYS = ("edge_coords", "phi", "replicas", "workload")


def _pad_instance(inst, q_pad, z_pad):
    """Re-pad a single instance to larger (Q, Z) with zero features."""
    q = inst["edge_mask"].shape[-1]
    z = inst["req_mask"].shape[-1]
    dq, dz = q_pad - q, z_pad - z
    out = dict(inst)
    out["edge_coords"] = jnp.pad(inst["edge_coords"], ((0, dq), (0, 0)))
    out["phi"] = jnp.pad(inst["phi"], ((0, dq), (0, 0)))
    out["replicas"] = jnp.pad(inst["replicas"], (0, dq))
    out["workload"] = jnp.pad(inst["workload"], ((0, dq), (0, 0)))
    out["w"] = jnp.pad(inst["w"], ((0, dq), (0, dq)))
    out["edge_mask"] = jnp.pad(inst["edge_mask"], (0, dq))
    out["req_src"] = jnp.pad(inst["req_src"], (0, dz))
    out["req_size"] = jnp.pad(inst["req_size"], (0, dz))
    out["req_mask"] = jnp.pad(inst["req_mask"], (0, dz))
    return out


@pytest.mark.parametrize("backend", BACKENDS)
def test_mask_invariance_of_encode_and_score(backend):
    """Padding extra edges/requests onto an instance must leave the valid
    region of the embeddings and log-probs unchanged (catches -1e9 and
    masked-norm leaks through softmax/batchnorm denominators).

    Leaks are caught exactly: whatever the padded rows hold, the real rows
    are bitwise identical. Against the unpadded instance the reductions run
    over longer axes, so f32 reassociation moves embeddings of magnitude ~2
    by up to ~1.3e-6 (each side is ~1e-6 from the float64 result, and in
    float64 the two agree to 3e-15); that comparison carries rtol=1e-6 on
    top of atol=1e-6."""
    params, state = corais_init(jax.random.PRNGKey(0), CFG)
    batch = _batch(b=1, q=4, z=6)
    inst = jax.tree.map(lambda x: x[0], batch)
    padded = _pad_instance(inst, q_pad=7, z_pad=11)
    garbage = dict(padded)
    for k in ("edge_coords", "phi", "workload", "replicas", "req_size"):
        a = np.asarray(padded[k]).copy()
        n = 4 if k in _PAD_EDGE_KEYS else 6
        a[n:] = 1e3 * (1.0 + np.arange(a[n:].size).reshape(a[n:].shape))
        garbage[k] = jnp.asarray(a)
    w = np.asarray(padded["w"]).copy()
    w[4:, :] = w[:, 4:] = 1e3
    garbage["w"] = jnp.asarray(w)
    garbage["req_src"] = padded["req_src"].at[6:].set(3)

    c0, h0, _ = corais_encode(params, state, inst, CFG)
    c1, h1, _ = corais_encode(params, state, padded, CFG)
    c2, h2, _ = corais_encode(params, state, garbage, CFG)
    np.testing.assert_array_equal(np.asarray(c2)[:4], np.asarray(c1)[:4])
    np.testing.assert_array_equal(np.asarray(h2)[:6], np.asarray(h1)[:6])
    np.testing.assert_allclose(np.asarray(c1)[:4], np.asarray(c0),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(h1)[:6], np.asarray(h0),
                               rtol=1e-6, atol=1e-6)

    lp0 = corais_score(params, c0, h0, inst["edge_mask"], CFG,
                       backend=backend)
    lp1 = corais_score(params, c1, h1, padded["edge_mask"], CFG,
                       backend=backend)
    lp2 = corais_score(params, c2, h2, garbage["edge_mask"], CFG,
                       backend=backend)
    np.testing.assert_array_equal(np.asarray(lp2)[:6, :4],
                                  np.asarray(lp1)[:6, :4])
    np.testing.assert_allclose(np.asarray(lp1)[:6, :4], np.asarray(lp0),
                               rtol=1e-6, atol=1e-6)
    # padded edges keep zero probability for real requests
    probs = np.exp(np.asarray(lp1))
    assert probs[:6, 4:].max() < 1e-6
    # and the decision itself is identical
    g0 = np.asarray(policy_decide(None, params, state, inst, CFG,
                                  backend=backend))
    g1 = np.asarray(policy_decide(None, params, state, padded, CFG,
                                  backend=backend))
    np.testing.assert_array_equal(g1[:6], g0)


@pytest.mark.parametrize("backend", BACKENDS)
def test_empty_round_gradients_are_finite(backend):
    """A round with no arrivals (every request masked) must give finite
    policy gradients: the trainer drops a non-finite update whole, so one
    empty round in a batch used to discard the batch's REINFORCE step."""
    params, state = corais_init(jax.random.PRNGKey(0), CFG)
    inst = jax.tree.map(lambda x: x[0], _batch(b=1, q=4, z=6))
    empty = dict(inst, req_mask=jnp.zeros_like(inst["req_mask"]))

    def loss(p):
        c, h, _ = corais_encode(p, state, empty, CFG)
        lp = corais_score(p, c, h, empty["edge_mask"], CFG, backend=backend)
        return jnp.sum(c) + jnp.sum(jnp.where(empty["req_mask"][:, None],
                                              lp, 0.0))

    grads = jax.grad(loss)(params)
    for g in jax.tree.leaves(grads):
        assert np.isfinite(np.asarray(g)).all()


def test_mask_invariance_of_engine_assignments():
    """Widening the engine's arrival padding (max_per_round) must not move
    any real request's assignment, for the policy and greedy backends."""
    pcfg = PolicyConfig(d_model=32, ff_hidden=64, edge_layers=1,
                        request_layers=1)
    params, pstate = corais_init(jax.random.PRNGKey(0), pcfg)
    q, rounds, dt = 5, 6, 0.25
    fns = {
        "policy": engine.resolve_assign_fn(
            "policy", params=params, policy_state=pstate, policy_cfg=pcfg),
        "greedy": engine.resolve_assign_fn("greedy"),
    }
    for name, fn in fns.items():
        outs = {}
        for pad in (16, 32):
            arr = materialize_rounds(scenario("uniform_iid"), q, rounds, dt,
                                     seed=0, max_per_round=pad)
            cfg = engine.EngineConfig(num_edges=q, num_rounds=rounds,
                                      round_interval=dt, max_per_round=pad)
            run = engine.make_rollout(cfg, fn)
            final, infos = run(engine.init_state(cfg, 0), arr,
                               jax.random.PRNGKey(1))
            mask = np.asarray(arr["mask"])
            outs[pad] = np.asarray(jax.device_get(infos["assign"]))[mask]
        np.testing.assert_array_equal(outs[16], outs[32], err_msg=name)


# -- engine + controller integration -----------------------------------------


def test_policy_backend_rollout_matches_across_score_backends():
    """Full batched rollouts driven by the policy must produce identical
    assignments whichever scoring backend computes the head."""
    pcfg = PolicyConfig(d_model=32, ff_hidden=64, edge_layers=1,
                        request_layers=1)
    params, pstate = corais_init(jax.random.PRNGKey(0), pcfg)
    q, rounds, dt = 4, 4, 0.25
    arr = materialize_rounds(scenario("uniform_iid"), q, rounds, dt, seed=2)
    cfg = engine.EngineConfig(num_edges=q, num_rounds=rounds,
                              round_interval=dt,
                              max_per_round=arr["mask"].shape[-1])
    finals = {}
    for backend in BACKENDS:
        fn = engine.resolve_assign_fn(
            "policy", params=params, policy_state=pstate, policy_cfg=pcfg,
            backend=backend)
        run = engine.make_rollout(cfg, fn)
        final, infos = run(engine.init_state(cfg, 2), arr,
                           jax.random.PRNGKey(0))
        finals[backend] = jax.device_get(infos["assign"])
    for backend in ("ref", "pallas"):
        np.testing.assert_array_equal(finals[backend], finals["xla"],
                                      err_msg=backend)


def test_make_decision_fn_modes():
    params, state = corais_init(jax.random.PRNGKey(0), CFG)
    batch = _batch(b=1)
    inst = jax.tree.map(lambda x: x[0], batch)
    for mode in ("greedy", "sample"):
        decide = make_decision_fn(params, state, CFG, mode=mode,
                                  num_samples=8)
        a = np.asarray(decide(inst, jax.random.PRNGKey(0)))
        assert a.shape == (12,) and a.dtype == np.int32 and a.max() < 5
    with pytest.raises(ValueError, match="decode mode"):
        policy_decide(None, params, state, inst, CFG, mode="beam")


# -- fused decode: parity, no-materialization, sampled dispatch --------------


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("k,normalize", [(1, True), (1, False), (3, True)])
def test_decode_backend_parity(backend, k, normalize):
    """corais_score_decode agrees with the materialized xla decode across
    every backend: identical winner indices, values <= 1e-5, batched and
    unbatched (candidate slots only up to the real edge count — beyond it
    the kernel's output is documented undefined)."""
    params, state = corais_init(jax.random.PRNGKey(0), CFG)
    batch = _batch(q=4, q_pad=6, z=9, z_pad=13)  # padded + odd Z
    c, h, _ = corais_encode(params, state, batch, CFG)
    ti0, tv0 = corais_score_decode(params, c, h, batch["edge_mask"], CFG,
                                   k=k, normalize=normalize, backend="xla")
    ti, tv = corais_score_decode(params, c, h, batch["edge_mask"], CFG,
                                 k=k, normalize=normalize, backend=backend)
    assert ti.shape == tv.shape == batch["req_mask"].shape + (k,)
    assert ti.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(ti), np.asarray(ti0))
    np.testing.assert_allclose(np.asarray(tv), np.asarray(tv0),
                               rtol=1e-5, atol=1e-5)
    # unbatched through the same entry
    inst = jax.tree.map(lambda x: x[0], batch)
    c1, h1, _ = corais_encode(params, state, inst, CFG)
    ti1, tv1 = corais_score_decode(params, c1, h1, inst["edge_mask"], CFG,
                                   k=k, normalize=normalize, backend=backend)
    ti1x, tv1x = corais_score_decode(params, c1, h1, inst["edge_mask"], CFG,
                                     k=k, normalize=normalize, backend="xla")
    np.testing.assert_array_equal(np.asarray(ti1), np.asarray(ti1x))
    np.testing.assert_allclose(np.asarray(tv1), np.asarray(tv1x),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend", BACKENDS)
def test_decode_matches_materialized_score(backend):
    """The fused decode's top-1 must be the argmax of the materialized
    log-prob matrix, and its log-prob the gathered matrix entry."""
    params, state = corais_init(jax.random.PRNGKey(0), CFG)
    batch = _batch(b=2, q=5, z=11)
    c, h, _ = corais_encode(params, state, batch, CFG)
    lp = corais_score(params, c, h, batch["edge_mask"], CFG, backend="xla")
    ti, tv = corais_score_decode(params, c, h, batch["edge_mask"], CFG,
                                 k=1, normalize=True, backend=backend)
    np.testing.assert_array_equal(np.asarray(ti)[..., 0],
                                  np.argmax(np.asarray(lp), axis=-1))
    gathered = np.take_along_axis(np.asarray(lp), np.asarray(ti), axis=-1)
    np.testing.assert_allclose(np.asarray(tv), gathered,
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend", BACKENDS)
def test_decode_mask_and_padding_invariance(backend):
    """Bucket-padding an instance (extra masked edges and requests) must
    not move any real request's fused-decode candidates."""
    params, state = corais_init(jax.random.PRNGKey(0), CFG)
    batch = _batch(b=1, q=4, z=6)
    inst = jax.tree.map(lambda x: x[0], batch)
    padded = _pad_instance(inst, q_pad=7, z_pad=11)
    c0, h0, _ = corais_encode(params, state, inst, CFG)
    c1, h1, _ = corais_encode(params, state, padded, CFG)
    for normalize in (True, False):
        ti0, tv0 = corais_score_decode(params, c0, h0, inst["edge_mask"],
                                       CFG, k=2, normalize=normalize,
                                       backend=backend)
        ti1, tv1 = corais_score_decode(params, c1, h1, padded["edge_mask"],
                                       CFG, k=2, normalize=normalize,
                                       backend=backend)
        np.testing.assert_array_equal(np.asarray(ti1)[:6], np.asarray(ti0))
        np.testing.assert_allclose(np.asarray(tv1)[:6], np.asarray(tv0),
                                   rtol=0, atol=1e-5)
        # padded edges never win a candidate slot for real requests
        assert np.asarray(ti1)[:6].max() < 4


def test_decode_rejects_unknown_backend():
    params, state = corais_init(jax.random.PRNGKey(0), CFG)
    batch = _batch(b=1)
    c, h, _ = corais_encode(params, state, batch, CFG)
    with pytest.raises(ValueError, match="unknown decode backend"):
        corais_score_decode(params, c, h, batch["edge_mask"], CFG,
                            backend="nope")


def _jaxpr_shapes(jaxpr, acc):
    """All aval shapes in a jaxpr, recursing into sub-jaxprs (pjit bodies,
    scan/cond branches, pallas_call kernel jaxprs)."""
    def subs(val):
        if hasattr(val, "jaxpr"):  # ClosedJaxpr
            yield val.jaxpr
        elif hasattr(val, "eqns"):  # Jaxpr
            yield val
        elif isinstance(val, (list, tuple)):
            for v in val:
                yield from subs(v)

    for v in list(jaxpr.invars) + list(jaxpr.outvars) + list(jaxpr.constvars):
        aval = getattr(v, "aval", None)
        if aval is not None and hasattr(aval, "shape"):
            acc.add(tuple(aval.shape))
    for eqn in jaxpr.eqns:
        for v in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                acc.add(tuple(aval.shape))
        for val in eqn.params.values():
            for sub in subs(val):
                _jaxpr_shapes(sub, acc)
    return acc


def test_fused_decode_never_materializes_zq():
    """The tentpole guarantee, asserted on the program itself: the fused
    decode head's jaxpr contains no (Z, Q)-shaped intermediate anywhere
    (sub-jaxprs included) once the Z-block is smaller than Z, while the
    materialized host path provably does. Q and Z are chosen distinct from
    every other dimension so the shape match is unambiguous."""
    from repro.kernels import ops
    q, z, d, bz = 5, 64, 16, 32  # bz < z: full (Z, Q) can't hide in a block
    c = jax.random.normal(jax.random.PRNGKey(0), (q, d)) * 0.3
    h = jax.random.normal(jax.random.PRNGKey(1), (z, d)) * 0.3
    wx = jax.random.normal(jax.random.PRNGKey(2), (d, d)) * 0.3
    wy = jax.random.normal(jax.random.PRNGKey(3), (d, d)) * 0.3
    mask = jnp.ones(q, bool)

    fused = jax.make_jaxpr(
        lambda c, h: ops.policy_score_decode(c, h, wx, wy, mask, k=1,
                                             normalize=False, bz=bz))(c, h)
    shapes = _jaxpr_shapes(fused.jaxpr, set())
    assert (z, q) not in shapes and (q, z) not in shapes, sorted(shapes)

    # sanity: the same walk catches the materialized path red-handed
    host = jax.make_jaxpr(
        lambda c, h: jnp.argmax(ops.policy_score(c, h, wx, wy, mask),
                                axis=-1))(c, h)
    assert (z, q) in _jaxpr_shapes(host.jaxpr, set())


def test_policy_decide_fused_greedy_matches_host():
    """Same greedy decision through the fused and materialized routes, with
    and without the log-softmax normalizer, every backend."""
    params, state = corais_init(jax.random.PRNGKey(0), CFG)
    batch = _batch(b=1, q=5, z=12)
    inst = jax.tree.map(lambda x: x[0], batch)
    a0 = np.asarray(policy_decide(None, params, state, inst, CFG))
    for backend in BACKENDS:
        for normalize in (True, False):
            a = np.asarray(policy_decide(None, params, state, inst, CFG,
                                         fused_decode=True,
                                         normalize=normalize,
                                         backend=backend))
            np.testing.assert_array_equal(a, a0, err_msg=f"{backend}")


def test_policy_decide_sampled_fused_matches_dense_at_full_k():
    """With num_candidates=None (K = Q) the kernel top-k carries the whole
    categorical distribution, so the fused sampled dispatch reproduces the
    dense one draw for draw under the same key."""
    params, state = corais_init(jax.random.PRNGKey(0), CFG)
    batch = _batch(b=1, q=5, z=12)
    inst = jax.tree.map(lambda x: x[0], batch)
    for seed in (0, 1, 2):
        k = jax.random.PRNGKey(seed)
        dense = policy_decide(k, params, state, inst, CFG, mode="sample",
                              num_samples=12)
        fused = policy_decide(k, params, state, inst, CFG, mode="sample",
                              num_samples=12, fused_decode=True)
        np.testing.assert_array_equal(np.asarray(dense), np.asarray(fused))


def test_topk_sampling_distribution():
    """Seeded statistical pin of the sampled dispatch distribution.

    Exact part: at K = Q the renormalized kernel candidate set scatters
    back to exactly the dense softmax. Statistical part: empirical marginals
    of categorical draws over the (Z, K) candidate values stay within a
    small total-variation distance of the renormalized truncated
    distribution (and of the dense distribution at K = Q)."""
    params, state = corais_init(jax.random.PRNGKey(0), CFG)
    batch = _batch(b=1, q=5, z=8)
    inst = jax.tree.map(lambda x: x[0], batch)
    c, h, _ = corais_encode(params, state, inst, CFG)
    lp = np.asarray(corais_score(params, c, h, inst["edge_mask"], CFG))
    z, q = lp.shape

    ti, tv = corais_score_decode(params, c, h, inst["edge_mask"], CFG,
                                 k=q, normalize=True, backend="pallas")
    scattered = np.full((z, q), -np.inf, np.float32)
    np.put_along_axis(scattered, np.asarray(ti), np.asarray(tv), axis=-1)
    np.testing.assert_allclose(np.exp(scattered), np.exp(lp),
                               rtol=1e-5, atol=1e-5)

    for k in (3, q):
        tik, tvk = corais_score_decode(params, c, h, inst["edge_mask"], CFG,
                                       k=k, normalize=True, backend="pallas")
        n = 4000
        slots = jax.random.categorical(
            jax.random.PRNGKey(7), jnp.asarray(tvk)[None], axis=-1,
            shape=(n, z))
        draws = np.take_along_axis(np.asarray(tik)[None],
                                   np.asarray(slots)[..., None],
                                   axis=-1)[..., 0]            # (n, z)
        emp = np.stack([(draws == e).mean(axis=0) for e in range(q)], -1)
        # renormalized truncated target
        p = np.exp(np.asarray(tvk))
        target = np.zeros((z, q))
        np.put_along_axis(target, np.asarray(tik), p / p.sum(-1, keepdims=True),
                          axis=-1)
        tv_dist = 0.5 * np.abs(emp - target).sum(axis=-1)
        assert tv_dist.max() < 0.05, (k, tv_dist.max())


def test_engine_policy_fused_backend_matches_policy():
    """Full batched rollouts through ASSIGN_FNS['policy-fused'] produce the
    same assignments as the materialized policy backend."""
    pcfg = PolicyConfig(d_model=32, ff_hidden=64, edge_layers=1,
                        request_layers=1)
    params, pstate = corais_init(jax.random.PRNGKey(0), pcfg)
    q, rounds, dt = 4, 4, 0.25
    arr = materialize_rounds(scenario("uniform_iid"), q, rounds, dt, seed=2)
    cfg = engine.EngineConfig(num_edges=q, num_rounds=rounds,
                              round_interval=dt,
                              max_per_round=arr["mask"].shape[-1])
    outs = {}
    for name in ("policy", "policy-fused"):
        fn = engine.resolve_assign_fn(
            name, params=params, policy_state=pstate, policy_cfg=pcfg,
            backend="pallas")
        run = engine.make_rollout(cfg, fn)
        _, infos = run(engine.init_state(cfg, 2), arr, jax.random.PRNGKey(0))
        outs[name] = jax.device_get(infos["assign"])
    np.testing.assert_array_equal(outs["policy-fused"], outs["policy"])


def test_make_decision_fn_fused_modes():
    """The compile-once serving entry with fused_decode: both modes return
    valid assignments and greedy matches the materialized decision fn."""
    params, state = corais_init(jax.random.PRNGKey(0), CFG)
    batch = _batch(b=1)
    inst = jax.tree.map(lambda x: x[0], batch)
    host = make_decision_fn(params, state, CFG)
    for mode in ("greedy", "sample"):
        decide = make_decision_fn(params, state, CFG, mode=mode,
                                  num_samples=8, fused_decode=True,
                                  normalize=mode != "greedy")
        a = np.asarray(decide(inst, jax.random.PRNGKey(0)))
        assert a.shape == (12,) and a.dtype == np.int32 and a.max() < 5
        if mode == "greedy":
            np.testing.assert_array_equal(
                a, np.asarray(host(inst, jax.random.PRNGKey(0))))
