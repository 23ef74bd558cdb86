"""Plain reference of the CoRaiS policy's decision scores (paper eqs 12-16).

Straight jax.numpy on one unbatched instance, written from the paper's
equations and the weight layout of ``weights.py``: edge and request
encoders (masked multi-head self-attention + FC, each followed by a norm
over the instance's real rows), the context decoder over
[f_hat, h_hat, f_q], and the clipped compatibility C*tanh(u) of every
(request, edge) pair. Masked edges score -inf. Within one request,
log-probability differences equal score differences, so the scores are
what a greedy decision is judged by.

The reference computes in float32 with every product at HIGHEST precision
(full float32 on the TPU). Its control rounds every matmul operand to
float8 (e4m3) first, products still accumulated in float32: the program's
own products, at the TPU's default precision, already take one bfloat16
pass, so float8 operands are the step below what it computes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
CONTROL_OPERANDS = jnp.float8_e4m3fn


def _matmul(operand_dtype):
    """x @ y at HIGHEST precision, operands first rounded to
    ``operand_dtype`` (None: as they are)."""
    if operand_dtype is None:
        rnd = lambda x: x  # noqa: E731
    else:
        rnd = lambda x: x.astype(operand_dtype).astype(jnp.float32)  # noqa: E731
    return lambda a, b: jnp.matmul(rnd(a), rnd(b), precision=HIGHEST)


def _linear(mm, p, x):
    return mm(x, p["w"]) + p["b"]


def _mha(mm, p, xq, xkv, mask, heads):
    """Multi-head attention; ``mask`` (nq, nk) True = attend."""
    q, k, v = mm(xq, p["wq"]), mm(xkv, p["wk"]), mm(xkv, p["wv"])
    nq, d = q.shape
    dh = d // heads
    qh = q.reshape(nq, heads, dh).transpose(1, 0, 2)
    kh = k.reshape(-1, heads, dh).transpose(1, 0, 2)
    vh = v.reshape(-1, heads, dh).transpose(1, 0, 2)
    logits = mm(qh, kh.transpose(0, 2, 1)) / jnp.sqrt(jnp.float32(dh))
    logits = jnp.where(mask[None], logits, -1e9)
    out = mm(jax.nn.softmax(logits, axis=-1), vh)
    return mm(out.transpose(1, 0, 2).reshape(nq, d), p["wo"])


def _norm(p, s, x, mask):
    """Eval-mode norm: running statistics once trained (count > 0), else
    the mean and variance over the real rows of this instance."""
    m = mask[:, None].astype(x.dtype)
    cnt = jnp.maximum(jnp.sum(m), 1)
    bmean = jnp.sum(x * m, 0) / cnt
    bvar = jnp.sum(jnp.square(x - bmean) * m, 0) / cnt
    trained = s["count"] > 0
    mean = jnp.where(trained, s["mean"], bmean)
    var = jnp.where(trained, s["var"], bvar)
    return (x - mean) * lax.rsqrt(var + 1e-5) * p["scale"] + p["bias"]


def _encoder(mm, layers, states, x, mask, heads):
    att = mask[:, None] & mask[None, :]
    for p, s in zip(layers, states):
        h = _norm(p["norm1"], s["norm1"],
                  x + _mha(mm, p["align"]["mha"], x, x, att, heads), mask)
        f = _linear(mm, p["fc"]["l2"],
                    jax.nn.relu(_linear(mm, p["fc"]["l1"], h)))
        x = _norm(p["norm2"], s["norm2"], h + f, mask) * mask[:, None]
    return x


def _masked_max(x, mask):
    m = jnp.max(jnp.where(mask[:, None], x, -jnp.inf), axis=0)
    return jnp.where(jnp.any(mask), m, jnp.zeros_like(m))


def scores(params, state, inst, *, heads: int, tanh_clip: float,
           feature_scale: float, operand_dtype=None):
    """(Z, Q) clipped compatibilities of one (padded) instance; masked
    edges -inf. float32; every product at HIGHEST precision, of operands
    rounded to ``operand_dtype`` (None: unrounded)."""
    mm = _matmul(operand_dtype)
    emask, rmask = inst["edge_mask"], inst["req_mask"]
    coords = inst["edge_coords"]
    scale = jnp.asarray([1, 1, 1, 1, 1] + [feature_scale] * 3, jnp.float32)
    ef = jnp.concatenate([coords, inst["phi"], inst["replicas"][:, None],
                          inst["workload"]], -1) * scale
    rf = jnp.concatenate([coords[inst["req_src"]], inst["req_size"][:, None]],
                         -1)
    f = _encoder(mm, params["edge_layers"], state["edge_layers"],
                 _linear(mm, params["edge_proj"], ef), emask, heads)
    h = _encoder(mm, params["req_layers"], state["req_layers"],
                 _linear(mm, params["req_proj"], rf), rmask, heads)
    ctx = jnp.concatenate([jnp.broadcast_to(_masked_max(f, emask), f.shape),
                           jnp.broadcast_to(_masked_max(h, rmask), f.shape), f],
                          -1)
    c = _mha(mm, params["ctx_mha"], ctx, h,
             jnp.broadcast_to(rmask[None, :], (f.shape[0], h.shape[0])), heads)
    u = mm(mm(h, params["w_py"]), mm(c, params["w_px"]).T)
    u = u / jnp.sqrt(jnp.float32(c.shape[-1]))
    return jnp.where(emask[None, :], tanh_clip * jnp.tanh(u), -jnp.inf)


@functools.lru_cache(maxsize=None)
def batched_scores(heads: int, tanh_clip: float, feature_scale: float,
                   control: bool = False):
    """Jitted scores over a leading batch of padded instances: the
    reference, or with ``control`` its float8-operand twin."""
    kw = dict(heads=heads, tanh_clip=tanh_clip, feature_scale=feature_scale,
              operand_dtype=CONTROL_OPERANDS if control else None)
    fn = lambda p, s, inst: scores(p, s, inst, **kw)  # noqa: E731
    return jax.jit(jax.vmap(fn, in_axes=(None, None, 0)))
