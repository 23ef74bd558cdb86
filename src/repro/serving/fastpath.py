"""Online serving fast path: compile-once, double-buffered decision loop.

The controller's `_policy_assign` already compiles one decision function
against a fixed padded shape; this module is the production version of that
idea, built for the paper's real-time regime (Q <= 100 edges, Z <= 1000
requests per round, millisecond decisions):

fixed padding buckets
    Live rounds vary in (q, z); jit recompiles per shape. The fast path
    quantizes every snapshot up to a small ladder of (q_pad, z_pad) buckets
    (:data:`DEFAULT_BUCKETS` covers the paper grid) so the steady state
    touches a handful of compiled executables, all warmed ahead of time by
    :meth:`DecisionFastPath.warmup`. Decisions are mask-invariant (pinned
    by tests/test_policy_stack.py), so bucket padding never changes an
    assignment.

fused in-kernel decode
    Buckets default to ``fused_decode=True`` — argmax/top-k happen inside
    the scoring kernel (see kernels/policy_score.py) and the round's (Z, Q)
    log-prob matrix is never materialized; the transfer back to the host is
    (z,) int32 instead of (Z, Q) f32. Greedy buckets also default to
    ``normalize=False``: the log-softmax normalizer cannot change an
    argmax, so serving skips it.

one packed transfer per round
    :meth:`submit` packs the padded snapshot into one flat int32 host
    buffer (:class:`PackedLayout`: each leaf in its own 128-word-aligned
    segment, float32 bit for bit, masks as 0/1), ships it with a single
    ``device_put`` and returns with the decision still in flight (jax
    dispatch is async); :meth:`result` blocks. The compiled decision
    program unpacks the buffer on the device. A ``device_put`` pays a fixed
    runtime cost per leaf, whatever its size, so one leaf in place of ten
    is what the packing saves. Each bucket keeps two such buffers
    (ping-pong), allocated once and reused: staging round n+1 never
    overwrites host memory an in-flight transfer of round n may still be
    reading. With ``donate=True`` (default off-CPU; CPU jax does not
    support donation) the packed buffer is donated to the call.

explicit SLOs
    :class:`SLOSpec` states the latency contract (p50/p95/p99 in ms);
    :func:`evaluate_slo` drives a fast path over a workload and returns a
    machine-checkable pass/fail report (benchmarks/policy_latency.py
    ``--fastpath`` writes it to results/slo_report.json; CI uploads it).
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Iterable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.inference import DecisionSpec, policy_decide
from repro.core.policy import PolicyConfig
from repro.platform import donate_default
from repro.tracing import span

#: (q_pad, z_pad) ladder covering the paper's serving grid (Q <= 100 edges,
#: Z <= 1000 requests/round). A snapshot lands in the smallest bucket that
#: holds it; oversize snapshots raise rather than silently recompile.
DEFAULT_BUCKETS = ((10, 100), (25, 250), (50, 500), (100, 1000))

#: Instance leaves staged per round, with their pad axis counts
#: ((n_q_axes, n_z_axes) interpretation is positional below).
_EDGE_KEYS = ("edge_coords", "phi", "replicas", "workload", "edge_mask")
_REQ_KEYS = ("req_src", "req_size", "req_mask")


def pad_instance(inst: dict, q_pad: int, z_pad: int) -> dict:
    """Zero-pad a host-side instance to (q_pad, z_pad) (numpy, no device
    work). Masks pad with False, so the policy's decision on the real rows
    is unchanged (mask invariance)."""
    q = int(np.shape(inst["edge_mask"])[-1])
    z = int(np.shape(inst["req_mask"])[-1])
    if q > q_pad or z > z_pad:
        raise ValueError(f"instance ({q}, {z}) exceeds pad ({q_pad}, {z_pad})")
    dq, dz = q_pad - q, z_pad - z
    out = dict(inst)
    for k in _EDGE_KEYS:
        a = np.asarray(inst[k])
        out[k] = np.pad(a, ((0, dq),) + ((0, 0),) * (a.ndim - 1))
    out["w"] = np.pad(np.asarray(inst["w"]), ((0, dq), (0, dq)))
    for k in _REQ_KEYS:
        out[k] = np.pad(np.asarray(inst[k]), (0, dz))
    return out


#: Every segment of a packed buffer starts at a multiple of this many
#: 32-bit words, so each slice on the device starts on a lane boundary.
_ALIGN_WORDS = 128
_PACKABLE = ("float32", "int32", "bool")


@dataclasses.dataclass(frozen=True)
class PackedLayout:
    """Where each leaf of a padded instance lives in one flat int32 buffer.

    ``leaves`` holds ``(key, shape, dtype name)`` in key order and
    ``offsets`` the word each leaf's segment starts at (a multiple of
    128); ``words`` is the buffer's length. float32 leaves are stored bit
    for bit through an int32 view (never as floats: an int32 read as a
    float32 may be a denormal), int32 leaves as they are, bool leaves as
    0/1. Built from the tree :func:`pad_instance` returns, so an instance
    that carries extra keys gets a layout of its own."""

    leaves: tuple[tuple[str, tuple[int, ...], str], ...]
    offsets: tuple[int, ...]
    words: int

    @classmethod
    def of(cls, padded: dict) -> "PackedLayout":
        leaves, offsets, end = [], [], 0
        for k in sorted(padded):
            a = np.asarray(padded[k])
            dtype = jax.dtypes.canonicalize_dtype(a.dtype).name
            if dtype not in _PACKABLE:
                raise TypeError(f"instance leaf {k!r} has dtype {dtype}; "
                                f"the packed stage holds {_PACKABLE}")
            leaves.append((k, a.shape, dtype))
            offsets.append(end)
            end += -(-a.size // _ALIGN_WORDS) * _ALIGN_WORDS
        return cls(tuple(leaves), tuple(offsets), end)

    @property
    def nbytes(self) -> int:
        return 4 * self.words

    def views(self, buf: np.ndarray) -> list[np.ndarray]:
        """Each leaf's segment of the host buffer ``buf``, shaped, as
        float32 for float32 leaves and int32 otherwise."""
        out = []
        for (_, shape, dtype), off in zip(self.leaves, self.offsets):
            seg = buf[off:off + math.prod(shape)]
            out.append((seg.view(np.float32) if dtype == "float32"
                        else seg).reshape(shape))
        return out

    def unpack(self, packed: jax.Array) -> dict:
        """The padded instance back from a packed buffer, in jnp: static
        slices, reshapes and bitcasts that XLA fuses into their users."""
        out = {}
        for (k, shape, dtype), off in zip(self.leaves, self.offsets):
            seg = lax.slice(packed, (off,), (off + math.prod(shape),))
            seg = seg.reshape(shape)
            if dtype == "float32":
                seg = lax.bitcast_convert_type(seg, jnp.float32)
            elif dtype == "bool":
                seg = seg != 0
            out[k] = seg
        return out


class _Lane:
    """One packed layout's compiled decision program and its two ping-pong
    host buffers, allocated once and restaged every round."""

    def __init__(self, layout: PackedLayout, fn):
        self.layout, self.fn = layout, fn
        self.bufs = [np.zeros(layout.words, np.int32) for _ in range(2)]
        self.views = [layout.views(b) for b in self.bufs]
        self.slot = 0

    def stage(self, inst: dict) -> np.ndarray:
        """Zero the next ping-pong buffer and write each leaf of ``inst``
        into the leading corner of its segment; returns the buffer."""
        slot = self.slot
        self.slot = 1 - slot
        buf = self.bufs[slot]
        buf.fill(0)
        for (k, _, _), view in zip(self.layout.leaves, self.views[slot]):
            a = np.asarray(inst[k])
            np.copyto(view[(*map(slice, a.shape), ...)], a,
                      casting="same_kind")
        return buf


@dataclasses.dataclass(frozen=True)
class SLOSpec:
    """Latency contract for one decision path, in milliseconds."""

    p50_ms: float
    p95_ms: float
    p99_ms: float
    name: str = "decision"

    def check(self, samples_ms: Sequence[float]) -> dict:
        """Measured percentiles vs the contract -> pass/fail report row."""
        s = np.asarray(list(samples_ms), np.float64)
        if s.size == 0:
            raise ValueError("no latency samples to check against the SLO")
        measured = {p: float(np.percentile(s, p)) for p in (50, 95, 99)}
        target = {50: self.p50_ms, 95: self.p95_ms, 99: self.p99_ms}
        ok = {p: measured[p] <= target[p] for p in measured}
        return {
            "name": self.name,
            "samples": int(s.size),
            "p50_ms": measured[50], "p50_slo_ms": target[50],
            "p95_ms": measured[95], "p95_slo_ms": target[95],
            "p99_ms": measured[99], "p99_slo_ms": target[99],
            "p50_ok": ok[50], "p95_ok": ok[95], "p99_ok": ok[99],
            "pass": all(ok.values()),
        }


class DecisionFastPath:
    """Compile-once, double-buffered policy decision loop.

    One instance owns, per padding bucket (and per :class:`PackedLayout`,
    should an instance carry extra keys): a jitted decision program that
    unpacks the packed buffer and runs
    :func:`repro.core.inference.policy_decide` (fused decode by default),
    and two ping-pong packed host buffers. The round loop
    is ``submit`` (stage + async dispatch) then ``result`` (block + strip
    padding); :meth:`decide` does both, :meth:`stream` overlaps them one
    round deep.

    ``donate=None`` resolves to True off-CPU (CPU jax warns and copies on
    donation, so it stays off there). Greedy mode reuses one constant PRNG
    key (the decode ignores it); sample mode folds the round counter into
    the seed so repeated rounds draw fresh candidates.
    """

    def __init__(self, params, policy_state, cfg: PolicyConfig,
                 spec: Optional[DecisionSpec] = None, *,
                 mode: str = "greedy", num_samples: int = 64,
                 buckets: Sequence[tuple[int, int]] = DEFAULT_BUCKETS,
                 fused_decode: bool = True,
                 normalize: Optional[bool] = None,
                 num_candidates: Optional[int] = None,
                 backend: Optional[str] = None,
                 donate: Optional[bool] = None, seed: int = 0):
        if donate is None:
            donate = donate_default()
        if spec is None:
            if normalize is None:
                # the normalizer cannot move a greedy argmax; sampling
                # needs true log-probs
                normalize = mode != "greedy"
            spec = DecisionSpec(mode=mode, num_samples=num_samples,
                                backend=backend, fused_decode=fused_decode,
                                num_candidates=num_candidates,
                                normalize=normalize)
        self.spec = spec
        self.mode = spec.mode
        self.buckets = tuple(sorted(tuple(b) for b in buckets))
        self.donate = donate
        self._params, self._state, self._cfg = params, policy_state, cfg
        # (bucket, sorted instance keys) -> lane
        self._lanes: dict[tuple, _Lane] = {}
        self._round = 0
        self._key0 = jax.random.PRNGKey(seed)
        self.compile_ms: dict[tuple[int, int], float] = {}

    # -- bucket machinery ---------------------------------------------------

    def bucket_for(self, q: int, z: int) -> tuple[int, int]:
        """Smallest bucket holding a (q, z) snapshot; raises when none do."""
        for b in self.buckets:
            if q <= b[0] and z <= b[1]:
                return b
        raise ValueError(
            f"snapshot ({q}, {z}) exceeds every fast-path bucket "
            f"{self.buckets}; add a larger bucket")

    def _decision_fn(self, layout: PackedLayout):
        params, state, cfg, spec = (self._params, self._state, self._cfg,
                                    self.spec)

        def decide(packed, key):
            return policy_decide(key, params, state, layout.unpack(packed),
                                 cfg, spec)

        return jax.jit(decide, donate_argnums=(0,) if self.donate else ())

    def _stage(self, inst: dict, bucket) -> tuple[_Lane, np.ndarray]:
        """Pack ``inst`` into the next ping-pong host buffer of its bucket
        and key set; returns the lane (layout and decision program) and
        the buffer. The first instance of a key set in a bucket derives the
        layout from :func:`pad_instance`."""
        sig = (bucket, tuple(sorted(inst)))
        lane = self._lanes.get(sig)
        if lane is None:
            layout = PackedLayout.of(pad_instance(inst, *bucket))
            lane = self._lanes[sig] = _Lane(layout, self._decision_fn(layout))
        return lane, lane.stage(inst)

    def _round_key(self):
        if self.mode == "greedy":
            return self._key0  # decode ignores it: constant, never re-split
        return jax.random.fold_in(self._key0, self._round)

    # -- decision loop ------------------------------------------------------

    def warmup(self, buckets: Optional[Sequence[tuple[int, int]]] = None):
        """Compile (and time) the decision executable of each bucket ahead
        of traffic; returns {bucket: compile_ms}."""
        for bucket in (buckets or self.buckets):
            bucket = tuple(bucket)
            zero = {
                "edge_coords": np.zeros((bucket[0], 2), np.float32),
                "phi": np.zeros((bucket[0], 2), np.float32),
                "replicas": np.ones(bucket[0], np.float32),
                "workload": np.zeros((bucket[0], 3), np.float32),
                "w": np.zeros((bucket[0], bucket[0]), np.float32),
                "ct": np.float32(1.0),
                "req_src": np.zeros(bucket[1], np.int32),
                "req_size": np.zeros(bucket[1], np.float32),
                "edge_mask": np.arange(bucket[0]) < 1,
                "req_mask": np.zeros(bucket[1], bool),
            }
            lane, packed = self._stage(zero, bucket)
            t0 = time.perf_counter()
            jax.block_until_ready(lane.fn(jax.device_put(packed), self._key0))
            self.compile_ms[bucket] = (time.perf_counter() - t0) * 1e3
        return dict(self.compile_ms)

    def submit(self, inst: dict):
        """Stage + dispatch one decision; returns an in-flight handle
        (jax async dispatch — the host is free as soon as this returns).

        Under a profiler session the call is the host span
        ``corais.fastpath.submit`` (arguments ``round``, the decision
        counter, and ``q``, ``z``, ``q_pad``, ``z_pad``) holding
        ``corais.fastpath.stage`` (bucket, packing into the ping-pong
        buffer), ``.transfer`` (the one ``device_put``; arguments
        ``leaves``, 1, and ``bytes``, the packed size) and ``.dispatch``
        (the call of the compiled decision program)."""
        n = self._round
        with span("fastpath.submit", round=n) as sp:
            with span("fastpath.stage"):
                q = int(np.shape(inst["edge_mask"])[-1])
                z = int(np.shape(inst["req_mask"])[-1])
                bucket = self.bucket_for(q, z)
                lane, packed = self._stage(inst, bucket)
            sp.set_metadata(q=q, z=z, q_pad=bucket[0], z_pad=bucket[1])
            with span("fastpath.transfer", leaves=1, bytes=packed.nbytes):
                dev = jax.device_put(packed)
            with span("fastpath.dispatch"):
                out = lane.fn(dev, self._round_key())
            self._round += 1
        return out, z, n

    def result(self, handle) -> np.ndarray:
        """Block on an in-flight decision; returns the (z,) int32 assignment
        with bucket padding stripped.

        Under a profiler session the call is the host span
        ``corais.fastpath.result`` (argument ``round``, that of its
        ``submit``) holding ``corais.fastpath.wait`` (until the device is
        done) and ``.fetch`` (the copy to the host and the strip)."""
        out, z, n = handle
        with span("fastpath.result", round=n):
            with span("fastpath.wait"):
                jax.block_until_ready(out)
            with span("fastpath.fetch"):
                return np.asarray(out)[:z]

    def decide(self, inst: dict) -> np.ndarray:
        """Synchronous submit+result."""
        return self.result(self.submit(inst))

    def stream(self, insts: Iterable[dict]):
        """Pipelined decision stream: round n+1 is staged and dispatched
        while round n's result is awaited (the double buffer exists for
        exactly this overlap). Yields (z,) assignments in order."""
        pending = None
        for inst in insts:
            nxt = self.submit(inst)
            if pending is not None:
                yield self.result(pending)
            pending = nxt
        if pending is not None:
            yield self.result(pending)


def evaluate_slo(fastpath: DecisionFastPath, insts: Sequence[dict],
                 slo: SLOSpec, *, warmup_rounds: int = 2) -> dict:
    """Drive the fast path over a workload and check the SLO contract.

    Replays ``insts`` through :meth:`DecisionFastPath.decide` (after
    warming exactly the padding buckets the workload will hit, plus
    ``warmup_rounds`` unmeasured decide passes per hit bucket to absorb
    dispatch-path warmup), then evaluates ``slo`` on the wall latencies of
    the measured passes. Returns the :meth:`SLOSpec.check` report plus
    bucket/compile metadata.
    """
    if not insts:
        raise ValueError("evaluate_slo needs at least one instance")
    # Warm exactly the buckets this workload routes to. The old gate
    # ("skip warmup when any compile_ms entry exists") meant a partial
    # warmup([...]) suppressed warmup entirely, so the first decision in a
    # still-cold bucket paid its compilation inside a measured SLO sample.
    first_in_bucket: dict[tuple[int, int], dict] = {}
    for inst in insts:
        q = int(np.shape(inst["edge_mask"])[-1])
        z = int(np.shape(inst["req_mask"])[-1])
        first_in_bucket.setdefault(fastpath.bucket_for(q, z), inst)
    cold = [b for b in first_in_bucket if b not in fastpath.compile_ms]
    if cold:
        fastpath.warmup(cold)
    for inst in first_in_bucket.values():
        for _ in range(warmup_rounds):
            fastpath.decide(inst)
    latencies_ms = []
    for inst in insts:
        t0 = time.perf_counter()
        fastpath.decide(inst)
        latencies_ms.append((time.perf_counter() - t0) * 1e3)
    report = slo.check(latencies_ms)
    report["buckets"] = [list(b) for b in fastpath.buckets]
    report["compile_ms"] = {f"{b[0]}x{b[1]}": ms
                            for b, ms in fastpath.compile_ms.items()}
    report["donate"] = fastpath.donate
    return report
