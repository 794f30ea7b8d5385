"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

Read with ``jax.profiler.ProfileData``: each TPU chip is a plane named
``/device:TPU:<n>`` whose ``XLA Ops`` line holds one event per device
operation; the benchmark's own host spans (``TraceAnnotation`` names
starting ``bench.``) sit on the host planes, on the same clock.

* busy: the union of a chip's op intervals inside the traced window;
  idle share is 1 - busy / window, averaged over chips;
* op time by name: summed durations, averaged over chips.  The device's
  ``XLA Ops`` line nests a loop's body ops inside the loop's own event,
  so control-flow ops (``while``, ``conditional``) are left out of busy
  time and of the sums: only the ops that do the work count;
* phase of an op: the benchmark's dispatch span (``bench.prefill`` or
  ``bench.decode``) that last started before the op did — the engine
  syncs after each jitted call, so a program's ops follow its dispatch;
* idle gaps: each gap in a chip's busy union is named by the innermost
  ``bench.`` span around its midpoint (what the host was doing); a gap of
  ``LONG_GAP_S`` or more also by the innermost of the program's and the
  runtime's own host events there, on the thread of the ``bench.``
  spans (``"bench.step > <event>"``), so a stall names its cause.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)")
CONTAINER = re.compile(r"^%(while|conditional)[.\s]")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
LONG_GAP_S = 0.05
PHASE_SPANS = ("bench.prefill", "bench.decode")


@dataclass(frozen=True)
class Event:
    name: str
    start: float        # ns
    end: float          # ns


@dataclass
class Trace:
    ops: Dict[int, List[Event]] = field(default_factory=dict)  # per chip
    spans: List[Event] = field(default_factory=list)           # host
    host: List[Event] = field(default_factory=list)  # others, spans' thread


def read(profile) -> Trace:
    """A ``jax.profiler.ProfileData`` -> device ops per chip, the
    benchmark's host spans, and the other host events of their thread."""
    tr = Trace()
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                evs = tr.ops.setdefault(int(m.group(1)), [])
                evs.extend(Event(e.name, e.start_ns, e.end_ns)
                           for e in line.events)
            elif not m:
                evs = [Event(e.name, e.start_ns, e.end_ns)
                       for e in line.events]
                spans = [e for e in evs if e.name.startswith(SPAN_PREFIX)]
                if spans:
                    tr.spans.extend(spans)
                    tr.host.extend(e for e in evs
                                   if not e.name.startswith(SPAN_PREFIX))
    for evs in tr.ops.values():
        evs.sort(key=lambda e: e.start)
    tr.spans.sort(key=lambda e: e.start)
    tr.host.sort(key=lambda e: e.start)
    return tr


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


@dataclass
class Reduction:
    window_s: float
    chips: int
    busy_s: float                     # mean over chips
    op_seconds: Dict[str, float]      # by op name, mean over chips
    phase_op_seconds: Dict[Tuple[str, str], float]  # (phase, op name)
    idle_by_span: Dict[str, float]    # idle seconds by host span, mean

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_time(self, patterns, phase: Optional[str] = None) -> float:
        """Seconds (mean over chips) of ops whose name matches any of the
        regular expressions ``patterns``, optionally only in ``phase``."""
        if phase is None:
            items = self.op_seconds.items()
        else:
            items = ((n, s) for (p, n), s in self.phase_op_seconds.items()
                     if p == phase)
        return sum(s for n, s in items
                   if any(re.search(p, n) for p in patterns))


def _phase_of(starts: List[float], names: List[str], t: float) -> str:
    i = bisect.bisect_right(starts, t) - 1
    return names[i].removeprefix(SPAN_PREFIX) if i >= 0 else "none"


def _span_at(spans: List[Event], t: float) -> str:
    inner = None
    for s in spans:
        if s.start > t:
            break
        if s.end >= t and (inner is None
                           or s.end - s.start < inner.end - inner.start):
            inner = s
    return inner.name if inner is not None else "none"


def reduce(tr: Trace, t0: float, t1: float,
           long_gap_s: float = LONG_GAP_S) -> Reduction:
    """Metrics of the window [t0, t1] (ns, the trace's clock)."""
    if not tr.ops:
        raise ValueError("the trace holds no TPU device plane with an "
                         f"{OPS_LINE!r} line")
    window = t1 - t0
    phase_spans = [s for s in tr.spans if s.name in PHASE_SPANS]
    starts = [s.start for s in phase_spans]
    names = [s.name for s in phase_spans]
    ctx_spans = [s for s in tr.spans if s.end >= t0 and s.start <= t1]
    ctx_host = [s for s in tr.host if s.end >= t0 and s.start <= t1]
    busy = 0.0
    by_name: Dict[str, float] = defaultdict(float)
    by_phase: Dict[Tuple[str, str], float] = defaultdict(float)
    idle: Dict[str, float] = defaultdict(float)
    for evs in tr.ops.values():
        ivs = []
        for e in evs:
            s, t = max(e.start, t0), min(e.end, t1)
            if t <= s or CONTAINER.match(e.name):
                continue
            ivs.append((s, t))
            by_name[e.name] += t - s
            by_phase[(_phase_of(starts, names, e.start), e.name)] += t - s
        merged = union(ivs)
        busy += sum(t - s for s, t in merged)
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                mid = (a + b) / 2
                name = _span_at(ctx_spans, mid)
                if (b - a) * 1e-9 >= long_gap_s:
                    cause = _span_at(ctx_host, mid)
                    if cause != "none":
                        name = f"{name} > {cause}"
                idle[name] += b - a
    n = len(tr.ops)
    ns = 1e-9 / n
    return Reduction(
        window_s=window * 1e-9, chips=n, busy_s=busy * ns,
        op_seconds={k: v * ns for k, v in by_name.items()},
        phase_op_seconds={k: v * ns for k, v in by_phase.items()},
        idle_by_span={k: v * ns for k, v in idle.items()})


def short_name(name: str) -> str:
    """An HLO op event's kind: its instruction name without the number
    and, for a kernel (``tpu_custom_call``), its output shape."""
    head, _, rest = name.partition(" = ")
    kind = re.sub(r"\.\d+$", "", head.lstrip("%"))
    if 'custom_call_target="tpu_custom_call"' in rest:
        kind = "kernel " + rest.split("{")[0]
    return kind


def breakdown(red: Reduction, top: int = 10) -> Dict[str, list]:
    """The device ops that took most time (by kind) and the idle time by
    what the host was doing, each at most ``top`` entries."""
    kinds: Dict[str, float] = defaultdict(float)
    for n, s in red.op_seconds.items():
        kinds[short_name(n)] += s
    ops = sorted(kinds.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(red.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in gaps]}
