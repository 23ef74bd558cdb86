"""CoRaiS matching-on-demand policy network (paper §IV-A, Fig. 6).

Edge encoder (L attention layers) + request encoder (K attention layers)
align heterogeneous features; the context decoder attends the system context
[f_hat, h_hat, f_q] over request embeddings; the policy head scores every
(edge, request) pair with C*tanh compatibilities and softmaxes over edges
(eqs 12-17). One forward pass yields the full factorized scheduling
distribution, so S-sample RL (§IV-B) needs exactly one network evaluation.

The forward is split into two shared entry points used identically by
training, the batched rollout engine, and the serving controller:

    corais_encode  — encoders + context decoder -> (c_emb, h_emb, state)
    corais_score   — the eq 16-17 head, dispatching over SCORE_BACKENDS
                     ("xla" einsum head | "ref" pure-jnp oracle | "pallas"
                     fused kernel with custom VJP); every implementation
                     lives in repro.kernels, nothing re-derives the math.

``corais_apply`` = encode + score and remains the one-call forward.

The encoder sublayer alignment mechanism is pluggable ("mha" | "mlp") to
realize the paper's FC1/FC2/FC3 ablation baselines with parameter-matched
MLPs (see core/ablations.py).
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from repro.nn import (
    batchnorm_apply,
    batchnorm_init,
    layernorm_apply,
    layernorm_init,
    linear_apply,
    linear_init,
    mha_apply,
    mha_init,
)
from repro.nn.module import split_keys, uniform_init

EDGE_FEATURES = 8   # coords(2) + phi coeffs(2) + replicas(1) + workload(3)
REQ_FEATURES = 3    # source coords(2) + data size(1)
# Schema-v3 tier extras (PolicyConfig.tier_features): per-node cloud flag +
# cache locality, per-request deadline slack / priority / source residency.
TIER_EDGE_FEATURES = 2   # tier(1) + cache_frac(1)
TIER_REQ_FEATURES = 3    # req_slack(1) + req_priority(1) + req_cached(1)


@dataclasses.dataclass(frozen=True)
class PolicyConfig:
    # d_model=256 lands the parameter count at the paper's "about 4 million
    # learnable parameters" with the stated L=5/K=3/8-head/512-FC layout.
    d_model: int = 256
    num_heads: int = 8
    edge_layers: int = 5        # L (paper: 5)
    request_layers: int = 3     # K (paper: 3)
    ff_hidden: int = 512        # FC hidden dim (paper: 512, ReLU)
    tanh_clip: float = 10.0     # C in eq (16)
    norm: str = "batch"         # "batch" (paper) | "layer" (ablation knob)
    edge_align: str = "mha"     # "mha" (CoRaiS) | "mlp" (FC1/FC3)
    req_align: str = "mha"      # "mha" (CoRaiS) | "mlp" (FC2/FC3)
    feature_scale: float = 0.1  # static input scaling for workload features
    score_backend: str = "xla"  # eq 16-17 head: "xla" | "ref" | "pallas"
    # Admission head (resilience subsystem): a per-request admit logit on
    # top of the shared encoders, trained jointly with dispatch on
    # fault-injected episodes. Off by default so fault-free checkpoints
    # keep their parameter count.
    admit_head: bool = False
    admit_hidden: int = 64
    admit_bias: float = 2.0     # initial logit offset: start near admit-all
    # Edge–cloud tier conditioning (schema v3): widen both encoders'
    # input projections with the tier/cache-locality and deadline-slack/
    # priority features the engine's round_instance exposes (zeros when an
    # instance predates the tier, e.g. oracle snapshots or static training
    # instances). Off by default so flat-tier checkpoints keep their
    # parameter count.
    tier_features: bool = False


# ---------------------------------------------------------------------------
# feature builders (jnp twins of instances.edge_features/request_features)
# ---------------------------------------------------------------------------


def edge_feature_dim(cfg: "PolicyConfig") -> int:
    return EDGE_FEATURES + (TIER_EDGE_FEATURES if cfg.tier_features else 0)


def req_feature_dim(cfg: "PolicyConfig") -> int:
    return REQ_FEATURES + (TIER_REQ_FEATURES if cfg.tier_features else 0)


def _tier_col(inst, key, like) -> jax.Array:
    """A (..., K, 1) tier-feature column, zeros when the instance predates
    schema v3 (oracle snapshots, static training instances)."""
    if key in inst:
        return inst[key][..., None].astype(jnp.float32)
    return jnp.zeros(like.shape[:-1] + (1,), jnp.float32)


def edge_features(inst, cfg: "PolicyConfig" = None) -> jax.Array:
    cols = [
        inst["edge_coords"],
        inst["phi"],
        inst["replicas"][..., None],
        inst["workload"],
    ]
    if cfg is not None and cfg.tier_features:
        cols.append(_tier_col(inst, "tier", inst["phi"]))
        cols.append(_tier_col(inst, "cache_frac", inst["phi"]))
    return jnp.concatenate(cols, axis=-1).astype(jnp.float32)


def request_features(inst, cfg: "PolicyConfig" = None) -> jax.Array:
    src = inst["req_src"][..., None].astype(jnp.int32)
    coords = jnp.take_along_axis(inst["edge_coords"], src, axis=-2)
    size = inst["req_size"][..., None]
    cols = [coords, size]
    if cfg is not None and cfg.tier_features:
        cols.append(_tier_col(inst, "req_slack", size))
        cols.append(_tier_col(inst, "req_priority", size))
        cols.append(_tier_col(inst, "req_cached", size))
    return jnp.concatenate(cols, axis=-1).astype(jnp.float32)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _align_init(key, cfg: PolicyConfig, kind: str):
    """Alignment sublayer: MHA (paper) or parameter-matched token-wise MLP.

    MHA holds 4*d^2 weights; the MLP uses d->2d->d (= 4*d^2) to keep the
    learnable-parameter count matched, as required for FC1/FC2/FC3."""
    d = cfg.d_model
    if kind == "mha":
        return {"mha": mha_init(key, d, cfg.num_heads)}
    k1, k2 = jax.random.split(key)
    return {
        "mlp": {  # bias-free so the count matches MHA's 4*d^2 exactly
            "l1": linear_init(k1, d, 2 * d, bias=False),
            "l2": linear_init(k2, 2 * d, d, bias=False),
        }
    }


def _norm_init(cfg: PolicyConfig):
    if cfg.norm == "batch":
        return batchnorm_init(cfg.d_model)
    return layernorm_init(cfg.d_model), {}


def _encoder_init(key, cfg: PolicyConfig, num_layers: int, align: str):
    layers, states = [], []
    for k in split_keys(key, num_layers):
        ka, kf1, kf2 = split_keys(k, 3)
        n1p, n1s = _norm_init(cfg)
        n2p, n2s = _norm_init(cfg)
        layers.append(
            {
                "align": _align_init(ka, cfg, align),
                "norm1": n1p,
                "fc": {
                    "l1": linear_init(kf1, cfg.d_model, cfg.ff_hidden),
                    "l2": linear_init(kf2, cfg.ff_hidden, cfg.d_model),
                },
                "norm2": n2p,
            }
        )
        states.append({"norm1": n1s, "norm2": n2s})
    return layers, states


def corais_init(key, cfg: PolicyConfig):
    keys = split_keys(key, 8)
    d = cfg.d_model
    edge_layers, edge_states = _encoder_init(keys[2], cfg, cfg.edge_layers, cfg.edge_align)
    req_layers, req_states = _encoder_init(keys[3], cfg, cfg.request_layers, cfg.req_align)
    params = {
        "edge_proj": linear_init(keys[0], edge_feature_dim(cfg), d),
        "req_proj": linear_init(keys[1], req_feature_dim(cfg), d),
        "edge_layers": edge_layers,
        "req_layers": req_layers,
        # eq (15): queries from [f_hat, h_hat, f_q] (3d), kv from requests
        "ctx_mha": mha_init(keys[4], 3 * d, cfg.num_heads, kv_dim=d, out_dim=d),
        "w_px": uniform_init(keys[5], (d, d), fan_in=d),
        "w_py": uniform_init(keys[6], (d, d), fan_in=d),
    }
    if cfg.admit_head:
        ka1, ka2 = jax.random.split(keys[7])
        params["admit"] = {
            # per-request MLP on [h_z ; f_hat]: the request embedding plus
            # the system context it would be admitted into
            "l1": linear_init(ka1, 2 * d, cfg.admit_hidden),
            "l2": linear_init(ka2, cfg.admit_hidden, 1),
        }
    state = {"edge_layers": edge_states, "req_layers": req_states}
    return params, state


# ---------------------------------------------------------------------------
# apply
# ---------------------------------------------------------------------------


def _masked_norm(norm_params, norm_state, x, mask, cfg: PolicyConfig, training: bool):
    """BatchNorm over valid tokens only (batch x nodes), or LayerNorm."""
    if cfg.norm == "layer":
        return layernorm_apply(norm_params, x), norm_state
    m = mask[..., None].astype(jnp.float32)
    cnt = jnp.maximum(jnp.sum(m), 1.0)
    if training:
        mean = jnp.sum(x * m, axis=tuple(range(x.ndim - 1))) / cnt
        var = jnp.sum(jnp.square(x - mean) * m, axis=tuple(range(x.ndim - 1))) / cnt
        momentum = 0.9
        new_state = {
            "mean": momentum * norm_state["mean"] + (1 - momentum) * mean,
            "var": momentum * norm_state["var"] + (1 - momentum) * var,
            "count": norm_state["count"] + 1,
        }
    else:
        trained = norm_state["count"] > 0
        bmean = jnp.sum(x * m, axis=tuple(range(x.ndim - 1))) / cnt
        bvar = jnp.sum(jnp.square(x - bmean) * m, axis=tuple(range(x.ndim - 1))) / cnt
        mean = jnp.where(trained, norm_state["mean"], bmean)
        var = jnp.where(trained, norm_state["var"], bvar)
        new_state = norm_state
    y = (x - mean) * jax.lax.rsqrt(var + 1e-5)
    return y * norm_params["scale"] + norm_params["bias"], new_state


def _align_apply(layer_align, x, mask, num_heads: int):
    if "mha" in layer_align:
        attn_mask = mask[..., None, None, :] & mask[..., None, :, None]
        return mha_apply(layer_align["mha"], x, mask=attn_mask, num_heads=num_heads)
    h = jax.nn.relu(linear_apply(layer_align["mlp"]["l1"], x))
    return linear_apply(layer_align["mlp"]["l2"], h)


def _encoder_apply(layers, states, x, mask, cfg: PolicyConfig, training: bool):
    new_states = []
    for layer, st in zip(layers, states):
        a = _align_apply(layer["align"], x, mask, cfg.num_heads)
        h, st1 = _masked_norm(layer["norm1"], st["norm1"], x + a, mask, cfg, training)
        f = linear_apply(layer["fc"]["l2"], jax.nn.relu(linear_apply(layer["fc"]["l1"], h)))
        x, st2 = _masked_norm(layer["norm2"], st["norm2"], h + f, mask, cfg, training)
        new_states.append({"norm1": st1, "norm2": st2})
        x = x * mask[..., None]
    return x, new_states


def _masked_max(x, mask):
    """Max over the valid rows; zeros where no row is valid (a round with
    no arrivals). A -inf there would enter the context query, and although
    the masked attention hides it from the forward, its weight gradient is
    -inf * 0 = NaN."""
    m = jnp.max(jnp.where(mask[..., None], x, -jnp.inf), axis=-2)
    return jnp.where(jnp.any(mask, axis=-1)[..., None], m, 0.0)


def corais_encode(params, state, inst, cfg: PolicyConfig, *,
                  training: bool = False):
    """Encoders + context decoder (eqs 12-15): the mask-invariant, fixed-
    shape front half of the forward.

    Returns (c_emb, h_emb, new_state): c_emb (..., Q, d) context-decoded
    edge embeddings, h_emb (..., Z, d) request embeddings. Feed both to
    :func:`corais_score` for the eq 16-17 head."""
    emask = inst["edge_mask"]
    rmask = inst["req_mask"]

    ef = edge_features(inst, cfg)
    # Static rescale keeps the heavy workload features in a trainable range;
    # the tier extras (flags/fractions in [0,1]) pass through unscaled.
    escale = [1, 1, 1, 1, 1] + [cfg.feature_scale] * 3
    if cfg.tier_features:
        escale += [1] * TIER_EDGE_FEATURES
    ef = ef * jnp.asarray(escale, jnp.float32)
    rf = request_features(inst, cfg)
    if cfg.tier_features:
        # deadline slack (capped upstream) and priority get the same static
        # rescale as the workload features; the 0/1 residency bit passes.
        rscale = [1, 1, 1] + [cfg.feature_scale, cfg.feature_scale, 1]
        rf = rf * jnp.asarray(rscale, jnp.float32)

    f = linear_apply(params["edge_proj"], ef)
    h = linear_apply(params["req_proj"], rf)
    f, est = _encoder_apply(params["edge_layers"], state["edge_layers"], f, emask, cfg, training)
    h, rst = _encoder_apply(params["req_layers"], state["req_layers"], h, rmask, cfg, training)

    f_hat = _masked_max(f, emask)  # (..., d)
    h_hat = _masked_max(h, rmask)
    q_ctx = jnp.concatenate(
        [
            jnp.broadcast_to(f_hat[..., None, :], f.shape),
            jnp.broadcast_to(h_hat[..., None, :], f.shape),
            f,
        ],
        axis=-1,
    )  # (..., Q, 3d)
    ctx_mask = rmask[..., None, None, :]  # attend only real requests
    c = mha_apply(
        params["ctx_mha"], q_ctx, kv_in=h, mask=ctx_mask, num_heads=cfg.num_heads
    )  # (..., Q, d)
    return c, h, {"edge_layers": est, "req_layers": rst}


# ---------------------------------------------------------------------------
# eq 16-17 head: one registry, three backends, zero duplicated math
# ---------------------------------------------------------------------------


def _score_xla(c_emb, h_emb, w_px, w_py, edge_mask, tanh_clip):
    from repro.kernels import ref
    return ref.policy_score_xla(c_emb, h_emb, w_px, w_py, edge_mask,
                                tanh_clip)


def _score_ref(c_emb, h_emb, w_px, w_py, edge_mask, tanh_clip):
    from repro.kernels import ref
    if c_emb.ndim == 2:
        return ref.policy_score_ref(c_emb, h_emb, w_px, w_py, edge_mask,
                                    tanh_clip)
    batch = c_emb.shape[:-2]
    q = c_emb.shape[-2]
    cf = c_emb.reshape((-1,) + c_emb.shape[-2:])
    hf = h_emb.reshape((-1,) + h_emb.shape[-2:])
    mf = jnp.broadcast_to(edge_mask, batch + (q,)).reshape((-1, q))
    out = jax.vmap(
        lambda c, h, m: ref.policy_score_ref(c, h, w_px, w_py, m, tanh_clip)
    )(cf, hf, mf)
    return out.reshape(batch + out.shape[-2:])


def _score_pallas(c_emb, h_emb, w_px, w_py, edge_mask, tanh_clip):
    from repro.kernels import ops
    return ops.policy_score(c_emb, h_emb, w_px, w_py, edge_mask,
                            tanh_clip=tanh_clip)


#: name -> fn(c_emb, h_emb, w_px, w_py, edge_mask, tanh_clip) -> (..., Z, Q)
SCORE_BACKENDS: dict[str, Callable] = {
    "xla": _score_xla,        # batched einsum head (kernels/ref.py)
    "ref": _score_ref,        # per-instance pure-jnp oracle (kernels/ref.py)
    "pallas": _score_pallas,  # fused kernel + custom VJP (kernels/policy_score.py)
}


def register_score_backend(name: str, fn: Callable) -> None:
    """Register a scoring implementation (see SCORE_BACKENDS signature)."""
    SCORE_BACKENDS[name] = fn


def list_score_backends() -> list[str]:
    return sorted(SCORE_BACKENDS)


def corais_score(params, c_emb, h_emb, edge_mask, cfg: PolicyConfig, *,
                 backend: str | None = None):
    """The eq 16-17 head on encoder outputs: log a_qz as (..., Z, Q).

    ``backend`` overrides ``cfg.score_backend``; every implementation is
    registered in :data:`SCORE_BACKENDS` and lives in :mod:`repro.kernels`.
    """
    name = backend or cfg.score_backend
    try:
        fn = SCORE_BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown score backend {name!r}; registered: "
            f"{', '.join(list_score_backends())}") from None
    return fn(c_emb, h_emb, params["w_px"], params["w_py"], edge_mask,
              cfg.tanh_clip)


# ---------------------------------------------------------------------------
# fused decode head: score + argmax/top-k without materializing (Z, Q)
# ---------------------------------------------------------------------------


def _decode_xla(c_emb, h_emb, w_px, w_py, edge_mask, tanh_clip, k, normalize):
    from repro.kernels import ref
    return ref.policy_score_decode_xla(c_emb, h_emb, w_px, w_py, edge_mask,
                                       tanh_clip, k, normalize)


def _decode_ref(c_emb, h_emb, w_px, w_py, edge_mask, tanh_clip, k, normalize):
    from repro.kernels import ref
    if c_emb.ndim == 2:
        return ref.policy_score_decode_ref(c_emb, h_emb, w_px, w_py,
                                           edge_mask, tanh_clip, k, normalize)
    batch = c_emb.shape[:-2]
    q = c_emb.shape[-2]
    cf = c_emb.reshape((-1,) + c_emb.shape[-2:])
    hf = h_emb.reshape((-1,) + h_emb.shape[-2:])
    mf = jnp.broadcast_to(edge_mask, batch + (q,)).reshape((-1, q))
    ti, tv = jax.vmap(
        lambda c, h, m: ref.policy_score_decode_ref(c, h, w_px, w_py, m,
                                                    tanh_clip, k, normalize)
    )(cf, hf, mf)
    return (ti.reshape(batch + ti.shape[-2:]),
            tv.reshape(batch + tv.shape[-2:]))


def _decode_pallas(c_emb, h_emb, w_px, w_py, edge_mask, tanh_clip, k,
                   normalize):
    from repro.kernels import ops
    return ops.policy_score_decode(c_emb, h_emb, w_px, w_py, edge_mask,
                                   tanh_clip=tanh_clip, k=k,
                                   normalize=normalize)


#: name -> fn(c_emb, h_emb, w_px, w_py, edge_mask, tanh_clip, k, normalize)
#: -> ((..., Z, K) int32 top edges, (..., Z, K) float32 values)
DECODE_BACKENDS: dict[str, Callable] = {
    "xla": _decode_xla,        # materialized head + lax.top_k (kernels/ref.py)
    "ref": _decode_ref,        # per-instance argsort oracle (kernels/ref.py)
    "pallas": _decode_pallas,  # fused kernel, (Z, Q) never leaves VMEM
}


def corais_score_decode(params, c_emb, h_emb, edge_mask, cfg: PolicyConfig,
                        *, k: int = 1, normalize: bool = True,
                        backend: str | None = None):
    """Fused eq 16-17 head + decode on encoder outputs: per-request top-k
    edges as ``(top_idx, top_val)``, both (..., Z, K). ``top_idx[..., 0]``
    is the greedy decision; with ``normalize=True`` values are eq-17
    log-probs, otherwise the clipped eq-16 compatibilities (same ranking,
    no normalizer — the serving fast path). Backend resolution mirrors
    :func:`corais_score` over :data:`DECODE_BACKENDS`."""
    name = backend or cfg.score_backend
    try:
        fn = DECODE_BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown decode backend {name!r}; registered: "
            f"{', '.join(sorted(DECODE_BACKENDS))}") from None
    return fn(c_emb, h_emb, params["w_px"], params["w_py"], edge_mask,
              cfg.tanh_clip, k, normalize)


def corais_admit(params, c_emb, h_emb, edge_mask, cfg: PolicyConfig):
    """Admission-head logits on encoder outputs: (..., Z) per-request
    admit/shed scores (sigmoid -> admit probability; > 0 -> admit under
    greedy decoding). Shares the dispatch encoders — the head sees each
    request embedding next to the pooled cluster context, so "is there
    anywhere this request can still meet its SLO" is one linear readout
    away. ``cfg.admit_bias`` offsets the logits so a fresh head starts
    near admit-all and training has to learn to shed."""
    if "admit" not in params:
        raise ValueError(
            "policy has no admission head; init with "
            "PolicyConfig(admit_head=True)")
    f_hat = _masked_max(c_emb, edge_mask)  # (..., d) cluster context
    x = jnp.concatenate(
        [h_emb, jnp.broadcast_to(f_hat[..., None, :], h_emb.shape)], axis=-1)
    hid = jax.nn.relu(linear_apply(params["admit"]["l1"], x))
    return linear_apply(params["admit"]["l2"], hid)[..., 0] + cfg.admit_bias


def corais_apply(params, state, inst, cfg: PolicyConfig, *,
                 training: bool = False, backend: str | None = None):
    """Full forward = corais_encode + corais_score.

    Returns (log_probs, new_state); log_probs: (..., Z, Q) log a_qz."""
    c, h, new_state = corais_encode(params, state, inst, cfg,
                                    training=training)
    log_probs = corais_score(params, c, h, inst["edge_mask"], cfg,
                             backend=backend)
    return log_probs, new_state
