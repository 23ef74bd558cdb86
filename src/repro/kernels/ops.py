"""Jit'd public wrappers for the Pallas kernels.

Interpret mode is decided by :func:`repro.platform.interpret_mode`: the
kernel bodies execute in the Pallas interpreter on the CPU (correctness
validation) and compile to Mosaic on a TPU. The pure-jnp oracles live in ref.py; tests sweep shapes/dtypes and
assert allclose kernel-vs-ref.
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention_fwd
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.mamba_scan import mamba_scan_fwd
from repro.kernels.policy_score import (policy_score_decode_fwd,
                                        policy_score_fwd)
from repro.platform import interpret_mode


@partial(jax.jit, static_argnames=("causal", "window", "bq", "bk"))
def flash_attention(q, k, v, *, causal=True, window=None, bq=128, bk=128):
    return flash_attention_fwd(q, k, v, causal=causal, window=window,
                               bq=bq, bk=bk, interpret=interpret_mode())


@partial(jax.jit, static_argnames=("window", "bk"))
def decode_attention(q, k_cache, v_cache, slot_pos, pos, *, window=None, bk=128):
    return decode_attention_fwd(q, k_cache, v_cache, slot_pos, pos,
                                window=window, bk=bk, interpret=interpret_mode())


@partial(jax.jit, static_argnames=("chunk", "bd"))
def mamba_scan(u, dt, B_mat, C_mat, A, *, chunk=128, bd=256):
    return mamba_scan_fwd(u, dt, B_mat, C_mat, A, chunk=chunk, bd=bd,
                          interpret=interpret_mode())


@partial(jax.jit, static_argnames=("tanh_clip", "bz"))
def policy_score(c_emb, h_emb, w_px, w_py, edge_mask, *, tanh_clip=10.0, bz=256):
    """Fused eq 16-17 head: any leading batch shape, custom-VJP backward."""
    return policy_score_fwd(c_emb, h_emb, w_px, w_py, edge_mask,
                            tanh_clip=tanh_clip, bz=bz, interpret=interpret_mode())


@partial(jax.jit, static_argnames=("tanh_clip", "k", "normalize", "bz"))
def policy_score_decode(c_emb, h_emb, w_px, w_py, edge_mask, *,
                        tanh_clip=10.0, k=1, normalize=True, bz=1024):
    """Fused score + greedy/top-k decode: (top_idx, top_val), (..., Z, K).

    Never materializes the (Z, Q) log-prob matrix — the sweep block lives
    in VMEM and only K entries per request come back. The default ``bz``
    covers Z <= 1024 in a single sweep."""
    return policy_score_decode_fwd(c_emb, h_emb, w_px, w_py, edge_mask,
                                   tanh_clip=tanh_clip, k=k,
                                   normalize=normalize, bz=bz,
                                   interpret=interpret_mode())


__all__ = ["flash_attention", "decode_attention", "mamba_scan",
           "policy_score", "policy_score_decode", "ref"]
