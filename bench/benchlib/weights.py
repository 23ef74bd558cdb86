"""Policy weights made from the seed, on the device, in one jitted call.

The tree has the layout the scheduler's policy reads (encoders, context
decoder, eq-16 projections) and the paper's init law (§V.A): every weight
and bias ~ U(-1/sqrt(fan_in), 1/sqrt(fan_in)), norm scale 1, bias 0. The
norm state has no running statistics (count 0), so both the program and
the reference normalize by the statistics of the instance's real rows.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, also one wider than 32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


def _uniform(key, shape, fan_in):
    b = 1.0 / math.sqrt(fan_in)
    return jax.random.uniform(key, shape, jnp.float32, -b, b)


def _linear(key, n_in, n_out):
    kw, kb = jax.random.split(key)
    return {"w": _uniform(kw, (n_in, n_out), n_in),
            "b": _uniform(kb, (n_out,), n_in)}


def _mha(key, dim, kv_dim, out_dim):
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {"wq": _uniform(kq, (dim, out_dim), dim),
            "wk": _uniform(kk, (kv_dim, out_dim), kv_dim),
            "wv": _uniform(kv, (kv_dim, out_dim), kv_dim),
            "wo": _uniform(ko, (out_dim, out_dim), out_dim)}


def _norm(d):
    return ({"scale": jnp.ones((d,), jnp.float32),
             "bias": jnp.zeros((d,), jnp.float32)},
            {"mean": jnp.zeros((d,), jnp.float32),
             "var": jnp.ones((d,), jnp.float32),
             "count": jnp.zeros((), jnp.float32)})


def _stack(key, n_layers, d, ff):
    layers, states = [], []
    for k in jax.random.split(key, n_layers):
        ka, k1, k2 = jax.random.split(k, 3)
        n1, s1 = _norm(d)
        n2, s2 = _norm(d)
        layers.append({"align": {"mha": _mha(ka, d, d, d)}, "norm1": n1,
                       "fc": {"l1": _linear(k1, d, ff), "l2": _linear(k2, ff, d)},
                       "norm2": n2})
        states.append({"norm1": s1, "norm2": s2})
    return layers, states


def _init(key, pol: dict):
    d, ff = pol["d_model"], pol["ff_hidden"]
    k = jax.random.split(key, 7)
    edge_layers, edge_states = _stack(k[2], pol["edge_layers"], d, ff)
    req_layers, req_states = _stack(k[3], pol["request_layers"], d, ff)
    params = {
        "edge_proj": _linear(k[0], pol["edge_features"], d),
        "req_proj": _linear(k[1], pol["req_features"], d),
        "edge_layers": edge_layers,
        "req_layers": req_layers,
        "ctx_mha": _mha(k[4], 3 * d, d, d),
        "w_px": _uniform(k[5], (d, d), d),
        "w_py": _uniform(k[6], (d, d), d),
    }
    return params, {"edge_layers": edge_states, "req_layers": req_states}


def make_policy(seed: int, pol: dict):
    """(params, state) for the policy widths ``pol``, on the default device."""
    init = jax.jit(lambda key: _init(key, pol))
    return jax.block_until_ready(init(seed_key(seed)))


def widths(config: dict, override: dict | None = None) -> dict:
    """The policy block of a configuration file, with a test's override."""
    pol = dict(config["policy"])
    pol.update(override or {})
    return pol


def program_config(pol: dict, backend: str):
    """The scheduler's PolicyConfig for these widths and score backend."""
    from repro.core.policy import PolicyConfig

    return PolicyConfig(d_model=pol["d_model"], num_heads=pol["num_heads"],
                        edge_layers=pol["edge_layers"],
                        request_layers=pol["request_layers"],
                        ff_hidden=pol["ff_hidden"], tanh_clip=pol["tanh_clip"],
                        feature_scale=pol["feature_scale"],
                        score_backend=backend)
