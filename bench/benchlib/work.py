"""Operations and bytes of the policy's work, from the real shapes of each
call (never a bucket's or slot table's padded shape), so the count reads the
same work whatever implements it.

FLOPs count matrix products only (2 per multiply-add); elementwise work
(norms, softmax, tanh) is left out. Bytes count each operand a kernel must
read from or write to HBM once, in float32.
"""
from __future__ import annotations

F32 = 4


def _encoder_flops(n: int, d: int, ff: int, layers: int) -> float:
    """One stack of self-attention + FC layers over n tokens."""
    proj = 4 * 2 * n * d * d            # wq, wk, wv, wo
    attn = 2 * 2 * n * n * d            # q k^T and attn v over all heads
    fc = 2 * 2 * n * d * ff
    return layers * (proj + attn + fc)


def encode_flops(q: int, z: int, pol: dict) -> float:
    """Encoders and context decoder (paper eqs 12-15) on one instance."""
    d, ff = pol["d_model"], pol["ff_hidden"]
    f = 2 * q * pol["edge_features"] * d + 2 * z * pol["req_features"] * d
    f += _encoder_flops(q, d, ff, pol["edge_layers"])
    f += _encoder_flops(z, d, ff, pol["request_layers"])
    # context decoder: queries from 3d-wide [f_hat, h_hat, f], kv from h
    f += 2 * q * 3 * d * d + 2 * 2 * z * d * d + 2 * 2 * q * z * d
    f += 2 * q * d * d
    return float(f)


def decode_kernel_work(instances, d: int, k: int = 1):
    """One call of the fused score + decode over ``instances``, the real
    (q, z) of each: c w_px, w_py (c w_px)^T, then h times that (d, q)
    matrix. The two (d, d) weights are one block for the whole grid, so a
    call reads them once, however many instances it holds.
    Returns (flops, bytes)."""
    flops = sum(2 * q * d * d + 2 * d * d * q + 2 * z * d * q
                for q, z in instances)
    nbytes = F32 * (2 * d * d + sum(q * d + z * d + q + 2 * z * k
                                    for q, z in instances))
    return float(flops), float(nbytes)


def decision_flops(q: int, z: int, pol: dict) -> float:
    """One served decision: encoders, context decoder and the fused head."""
    return encode_flops(q, z, pol) + decode_kernel_work(
        [(q, z)], pol["d_model"])[0]
