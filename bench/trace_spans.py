"""Device idle time in a profiler trace, named by the program's spans.

The program wraps its host work in ``repro.<name>`` annotations
(``repro.runtime.telemetry.Span``); they sit on the trace's host plane,
beside the harness's ``bench.<what>`` spans. This module finds the stretches of the traced
window in which no operation ran on a device, as ``trace_reduce`` does,
and names each after the host work it waited for:

* each gap is labelled ``<harness span>/<program span>``: the harness
  span that covers most of it and the innermost (shortest) program span
  that covers most of it; a gap no program span covers keeps the harness
  span alone (``none`` where neither covers it);
* ``idle_by_span``: for each innermost program span, the idle seconds it
  covers; ``idle_by_harness`` the same for the harness spans. Both are
  averaged over the devices, so each sums to at most ``idle_s``.

The two planes share a clock only roughly: on a TPU v5e host the device
plane read 1.1–1.8 ms early (a program starting before the span that
dispatched it). ``device_lead`` bounds that lead from the decode steps;
it says how far a gap's edges may sit from where the spans put them.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import heapq
from typing import Dict, List, Tuple

from bench import trace_reduce as T
from repro.runtime.telemetry import SPAN_PREFIX
MATCH_NS = 5_000_000     # a step's program lies within 5 ms of its spans

Interval = Tuple[int, int]
Segment = Tuple[int, int, str]


@dataclasses.dataclass
class SpanIdle:
    window_s: float
    idle_s: float                               # averaged over devices
    idle_gaps: List[Tuple[str, float]]          # the longest, labelled
    idle_by_span: Dict[str, float]
    idle_by_harness: Dict[str, float]


def innermost(spans: List[Tuple[str, Interval]]) -> List[Segment]:
    """The timeline the spans cover, cut at their edges: (start, end,
    name of the shortest span covering the piece), in time order."""
    edges = sorted({x for _, iv in spans for x in iv})
    by_start = sorted(spans, key=lambda sp: sp[1][0])
    active: List[Tuple[int, int, str]] = []     # (duration, end, name)
    out: List[Segment] = []
    j = 0
    for a, b in zip(edges, edges[1:]):
        while j < len(by_start) and by_start[j][1][0] <= a:
            name, (s, e) = by_start[j]
            heapq.heappush(active, (e - s, e, name))
            j += 1
        while active and active[0][1] <= a:
            heapq.heappop(active)
        if not active:
            continue
        name = active[0][2]
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


class Cover:
    """Which names cover how much of an interval, from ``innermost``."""

    def __init__(self, spans: List[Tuple[str, Interval]]):
        self.segs = innermost(spans)
        self.starts = [s for s, _, _ in self.segs]

    def of(self, iv: Interval) -> Dict[str, int]:
        out: Dict[str, int] = collections.Counter()
        i = max(0, bisect.bisect_right(self.starts, iv[0]) - 1)
        while i < len(self.segs) and self.segs[i][0] < iv[1]:
            s, e, name = self.segs[i]
            ov = min(e, iv[1]) - max(s, iv[0])
            if ov > 0:
                out[name] += ov
            i += 1
        return out


def host_spans(pd):
    """(window or None, harness spans, program spans) from the host
    planes; span names lose their prefix."""
    window = None
    harness: List[Tuple[str, Interval]] = []
    program: List[Tuple[str, Interval]] = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for name, s, e, _ in T._events(line):
                if name == T.WINDOW:
                    window = (s, e) if window is None else \
                        (min(window[0], s), max(window[1], e))
                elif name.startswith(T.PREFIX):
                    harness.append((name[len(T.PREFIX):], (s, e)))
                elif name.startswith(SPAN_PREFIX):
                    program.append((name[len(SPAN_PREFIX):], (s, e)))
    return window, harness, program


def device_lead(pd, program: str = "serve_step",
                dispatch: str = "session.dispatch",
                wait: str = "session.decode.wait"):
    """(lo, hi) seconds by which the device plane reads ahead of the host
    plane, or None without a matched step. Each run of a program whose
    name contains ``program`` starts after the ``dispatch`` span that
    enqueued it began, and ends before the ``wait`` span that waited for
    it ends; both spans are matched to the run within ``MATCH_NS``."""
    _, _, spans = host_spans(pd)
    starts = sorted(iv[0] for name, iv in spans if name == dispatch)
    ends = sorted(iv[1] for name, iv in spans if name == wait)
    lo = hi = None
    for plane in T.device_planes(pd):
        mods = next((l for l in plane.lines if l.name == "XLA Modules"),
                    None)
        for name, s, e, _ in (T._events(mods) if mods else ()):
            if program not in name:
                continue
            i = bisect.bisect_right(starts, s + MATCH_NS) - 1
            j = bisect.bisect_left(ends, e - MATCH_NS)
            if i >= 0:
                lo = max(lo, starts[i] - s) if lo is not None \
                    else starts[i] - s
            if j < len(ends):
                hi = min(hi, ends[j] - e) if hi is not None else ends[j] - e
    if lo is None or hi is None:
        return None
    return lo / 1e9, hi / 1e9


def attribute(pd, top: int = 10) -> SpanIdle:
    window, harness, program = host_spans(pd)
    ops_by_dev = []
    for plane in T.device_planes(pd):
        ops = next(l for l in plane.lines if l.name == "XLA Ops")
        ops_by_dev.append([(s, e) for _, s, e, _ in T._events(ops)])
    if window is None:
        every = [iv for ops in ops_by_dev for iv in ops]
        window = (min(s for s, _ in every), max(e for _, e in every)) \
            if every else (0, 0)
    hcover, pcover = Cover(harness), Cover(program)
    idle_ns = 0
    by_span, by_harness = collections.Counter(), collections.Counter()
    gaps: List[Tuple[str, float]] = []
    for ops in ops_by_dev:
        merged = T._merge([c for iv in ops if (c := T._clip(iv, window))])
        edges = [window[0]] + [x for iv in merged for x in iv] + [window[1]]
        for s, e in zip(edges[::2], edges[1::2]):
            if e <= s:
                continue
            idle_ns += e - s
            h, p = hcover.of((s, e)), pcover.of((s, e))
            by_harness.update(h)
            by_span.update(p)
            label = max(h, key=h.get) if h else "none"
            if p:
                label += "/" + max(p, key=p.get)
            gaps.append((label, (e - s) / 1e9))
    n = max(1, len(ops_by_dev))
    return SpanIdle(
        window_s=(window[1] - window[0]) / 1e9, idle_s=idle_ns / n / 1e9,
        idle_gaps=sorted(gaps, key=lambda g: -g[1])[:top],
        idle_by_span={k: v / n / 1e9 for k, v in by_span.items()},
        idle_by_harness={k: v / n / 1e9 for k, v in by_harness.items()})
