"""Reduction of a JAX profiler trace to the benchmark's numbers.

Reads the ``.xplane.pb`` that ``jax.profiler.trace`` writes, with nothing
but JAX (``jax.profiler.ProfileData``). Device operations are the events on
the "XLA Ops" lines of the ``/device:TPU:<n>`` planes; host spans are the
``bench.*`` annotations the benchmark's own files open around their calls
(``jax.profiler.TraceAnnotation``). Both share the trace's clock.

* busy: the union of the intervals in which any operation ran on a device,
  clipped to the window;
* kernel time: the summed durations of the operations whose name (the
  HLO instruction, on a TPU) contains the kernel's name;
* gap attribution: each idle interval of the device, split over the host
  spans open during it.
"""
from __future__ import annotations

import dataclasses
import glob
import os

NS = 1e-9
#: Per-event fields that may name the operation or the scope it came from.
_NAME_STATS = ("hlo_op", "long_name", "tf_op", "name", "hlo_category")


@dataclasses.dataclass
class Op:
    name: str
    start: int      # ns on the trace clock
    end: int
    text: str       # name plus every naming stat, for substring matching
    device: str


@dataclasses.dataclass
class Span:
    name: str
    start: int
    end: int


@dataclasses.dataclass
class Trace:
    ops: list       # [Op], device operations, every device
    spans: list     # [Span], bench.* host spans
    devices: list   # device plane names

    def window(self, name: str = "bench.window") -> tuple[int, int]:
        """(start, end) of the host span ``name``; raises when absent."""
        hits = [s for s in self.spans if s.name == name]
        if not hits:
            raise ValueError(f"no host span {name!r} in the trace")
        return min(s.start for s in hits), max(s.end for s in hits)


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _stats(ev) -> dict:
    try:
        return {str(k): v for k, v in ev.stats}
    except (TypeError, ValueError):
        return {}


def load(path: str, span_prefix: str = "bench.") -> Trace:
    """Read one .xplane.pb (or the newest under a trace directory)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    pd = ProfileData.from_file(path)
    ops, spans, devices = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane.name)
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    st = _stats(ev)
                    text = " ".join([ev.name] + [str(st[k]) for k in _NAME_STATS
                                                 if k in st])
                    s = int(ev.start_ns)
                    ops.append(Op(ev.name, s, s + int(ev.duration_ns), text,
                                  plane.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(span_prefix):
                        s = int(ev.start_ns)
                        spans.append(Span(ev.name, s, s + int(ev.duration_ns)))
    ops.sort(key=lambda o: o.start)
    spans.sort(key=lambda s: s.start)
    return Trace(ops, spans, devices)


def union(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """Merged (start, end) intervals clipped to [lo, hi]."""
    out: list[list[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_s(trace: Trace, lo: int, hi: int) -> float:
    """Seconds in [lo, hi] in which an operation ran, averaged over the
    devices in the trace."""
    if not trace.devices:
        return 0.0
    total = 0
    for dev in trace.devices:
        total += sum(e - s for s, e in union(
            ((o.start, o.end) for o in trace.ops if o.device == dev), lo, hi))
    return total * NS / len(trace.devices)


def ops_in(trace: Trace, lo: int, hi: int) -> list:
    return [o for o in trace.ops if o.start >= lo and o.end <= hi]


def kernel_time(ops, kernel: str) -> tuple[float, int]:
    """(seconds, events) of the operations that name ``kernel``."""
    hits = [o for o in ops if kernel in o.text]
    return sum(o.end - o.start for o in hits) * NS, len(hits)


def top_ops(ops, n: int = 10) -> list:
    """[[name, seconds]] of the n operations that took the most time,
    summed by name."""
    by: dict[str, int] = {}
    for o in ops:
        by[o.name] = by.get(o.name, 0) + (o.end - o.start)
    return [[k, v * NS] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:n]]


def _segments(spans) -> list[tuple[int, int, str]]:
    """Disjoint (start, end, name) pieces of the host timeline, each owned
    by the innermost (latest-starting) span open in it."""
    events = sorted([(sp.start, 1, i) for i, sp in enumerate(spans)]
                    + [(sp.end, 0, i) for i, sp in enumerate(spans)])
    active: set[int] = set()
    out, prev = [], None
    for t, kind, i in events:
        if prev is not None and t > prev and active:
            owner = max(active, key=lambda j: spans[j].start)
            out.append((prev, t, spans[owner].name))
        (active.add if kind else active.discard)(i)
        prev = t
    return out


def idle_gaps(trace: Trace, lo: int, hi: int, n: int = 10) -> list:
    """[[host span, seconds]]: the device's idle time in [lo, hi] (first
    device), split over the innermost bench.* span open in each part of
    it ("no span" where none is), largest first."""
    dev = trace.devices[0] if trace.devices else None
    busy = union(((o.start, o.end) for o in trace.ops if o.device == dev),
                 lo, hi)
    gaps, cur = [], lo
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        gaps.append((cur, hi))
    segs = _segments([s for s in trace.spans if s.name != "bench.window"])
    by: dict[str, int] = {}
    j = 0
    for gs, ge in gaps:
        covered = 0
        while j < len(segs) and segs[j][1] <= gs:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < ge:
            a, b = max(segs[k][0], gs), min(segs[k][1], ge)
            if b > a:
                by[segs[k][2]] = by.get(segs[k][2], 0) + (b - a)
                covered += b - a
            k += 1
        if ge - gs > covered:
            by["no span"] = by.get("no span", 0) + (ge - gs - covered)
    return [[k, v * NS] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:n]]
