"""Roofline share (%) of the fused score + decode kernel over every
simulated round of the traced calls: one kernel call a round, holding the
batch's instances, work counted at each instance's real arrivals.
Layer: kernels/policy_score."""
from benchlib import readers, work

#: the fused decode's operation in the TPU trace (its pallas_call name)
KERNEL = "policy_score_decode"


def read(data):
    if "rounds" not in data:
        return None
    data.setdefault("kernel_calls", {})[KERNEL] = data["rounds"]
    return readers.kernel_roofline(data, KERNEL, work.decode_kernel_work)
