"""Shared arithmetic of the per-layer metric readers (metrics/*.py).

A reader gets the driver's ``layer_data`` and returns a number, or None
when there is nothing to read (no trace, no device plane, no kernel event);
the harness then leaves the metric out of the line. A share of a roofline
or a peak is never returned as 0 for lack of data.
"""
from __future__ import annotations

import statistics

from benchlib import peaks
from benchlib import trace as tr


def median_ms(data: dict, key: str):
    xs = data.get(key)
    if xs is None or len(xs) == 0:
        return None
    return statistics.median(float(x) for x in xs) * 1e3


def _traced(data: dict):
    t = data.get("trace")
    if t is None or not t.devices:
        return None
    return t, data["lo"], data["hi"]


def busy_seconds(data: dict):
    got = _traced(data)
    if got is None:
        return None
    t, lo, hi = got
    return tr.busy_s(t, lo, hi)


def idle_share(data: dict):
    """% of the traced window in which no operation ran on the device."""
    got = _traced(data)
    if got is None:
        return None
    t, lo, hi = got
    return 100.0 * (1.0 - tr.busy_s(t, lo, hi) / ((hi - lo) * tr.NS))


def kernel_roofline(data: dict, kernel: str, work):
    """% of its roofline a kernel reaches over the window: the larger of
    FLOPs over peak and bytes over bandwidth, summed over the window's calls
    (``data["kernel_calls"][kernel]``: per call, the real (q, z) of each
    instance it holds; ``work(instances, d) -> (flops, bytes)``), over the
    kernel's summed device time. None where the trace holds no operation of
    that name, or another number of them than the calls counted: the work
    would then be charged to time that is not its own."""
    got = _traced(data)
    if got is None:
        return None
    t, lo, hi = got
    secs, n = tr.kernel_time(tr.ops_in(t, lo, hi), kernel)
    calls = data["kernel_calls"][kernel]
    if n == 0 or n != len(calls) or secs <= 0:
        return None
    pk = peaks.peak(data["device_kind"])
    d = data["pol"]["d_model"]
    flops = sum(work(c, d)[0] for c in calls)
    nbytes = sum(work(c, d)[1] for c in calls)
    bound = max(flops / pk["flops_per_s"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * bound / secs


def mfu(flops: float, seconds: float, device_kind: str):
    """% of the chip's peak FLOP rate that ``flops`` in ``seconds`` is."""
    if seconds <= 0:
        return None
    return 100.0 * flops / seconds / peaks.peak(device_kind)["flops_per_s"]

