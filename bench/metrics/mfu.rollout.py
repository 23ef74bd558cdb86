"""Whole-rollout share (%) of the chip's peak: the policy FLOPs of every
decision of the traced calls (real edges and arrivals) over their time."""
from benchlib import readers, work


def read(data):
    if "rounds" not in data or readers.busy_seconds(data) is None:
        return None  # no chip in the trace: no peak to share
    flops = sum(work.decision_flops(q, z, data["pol"])
                for instances in data["rounds"] for q, z in instances)
    return readers.mfu(flops, data["window_host_s"], data["device_kind"])
