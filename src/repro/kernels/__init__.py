"""Pallas TPU kernels for the perf-critical hot spots, with jnp oracles.

flash_attention  — blocked causal/SWA prefill attention (online softmax)
decode_attention — GQA flash-decode against a rolling KV cache
mamba_scan       — chunked selective scan (mamba-1)
policy_score     — fused CoRaiS policy head (paper eqs 16-17)

Use via repro.kernels.ops (jit'd wrappers; interpret mode on the CPU, Mosaic
on a TPU — decided by repro.platform.interpret_mode).
"""
from repro.kernels import ops, ref

__all__ = ["ops", "ref"]
