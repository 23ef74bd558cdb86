"""Roofline share (%) of the fused score + decode kernel over the served
decisions, work counted at each round's real edges and requests (one
instance a call). Layer: kernels/policy_score."""
from benchlib import readers, work

#: the fused decode's operation in the TPU trace (its pallas_call name)
KERNEL = "policy_score_decode"


def read(data):
    if "calls" not in data:
        return None
    data.setdefault("kernel_calls", {})[KERNEL] = [[c] for c in data["calls"]]
    return readers.kernel_roofline(data, KERNEL, work.decode_kernel_work)
