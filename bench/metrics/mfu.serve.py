"""Whole-decision share (%) of the chip's peak: the policy FLOPs of the
served decisions (encoders, context decoder, head, at real shapes) over the
sum of their submit-to-result times."""
from benchlib import readers, work


def read(data):
    if "calls" not in data or readers.busy_seconds(data) is None:
        return None  # no chip in the trace: no peak to share
    flops = sum(work.decision_flops(q, z, data["pol"]) for q, z in data["calls"])
    return readers.mfu(flops, float(sum(data["turnaround_s"])),
                       data["device_kind"])
