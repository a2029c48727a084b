"""A profiler trace (``.xplane.pb``) -> busy time, program time, breakdown.

Device planes are the ``/device:...`` planes that hold an ``XLA Ops`` line,
one event per executed operation (named by its HLO text, ``%name = ...``);
their ``XLA Modules`` line has one event per executed program (jitted
function, named ``jit_<fn>(<fingerprint>)``). Control-flow operations
(``while``, ``conditional``, ``call``) span the operations they run, so
they count towards busy time but not towards the top operations.
The harness wraps its own calls in ``jax.profiler.TraceAnnotation`` spans
named ``bench.<what>``; one of them, ``bench.trace_window``, marks the
traced window. All times are nanoseconds on the trace's one clock.

* busy: the union of the operation intervals inside the window, per
  device, averaged over the devices;
* programs: each program's executions inside the window, with their
  start (seconds after the window opened) and device time;
* top operations: device time summed by ``<program>/<operation>``;
* idle gaps: the stretches of the window in which no operation ran on a
  device, each labelled with the harness span that overlaps it most.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
from typing import Dict, List, Optional, Tuple

PREFIX = "bench."
WINDOW = "bench.trace_window"

Interval = Tuple[int, int]


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    n_devices: int
    programs: Dict[str, List[Tuple[float, float]]]  # name -> (start, s)
    top_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]
    spans: Dict[str, float]              # harness span -> seconds in window


def load(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path))


def _events(line):
    for e in line.events:
        yield e.name, int(e.start_ns), int(e.start_ns + e.duration_ns), e


def _merge(iv: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _clip(iv: Interval, w: Interval) -> Optional[Interval]:
    s, e = max(iv[0], w[0]), min(iv[1], w[1])
    return (s, e) if e > s else None


def _overlap(a: Interval, b: Interval) -> int:
    return max(0, min(a[1], b[1]) - max(a[0], b[0]))


def device_planes(pd) -> list:
    return [p for p in pd.planes if p.name.startswith("/device:")
            and any(l.name == "XLA Ops" for l in p.lines)]


def op_name(text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return text.split(" = ", 1)[0].lstrip("%")


def program_name(text: str) -> str:
    """``jit_prefill_step(1234)`` -> ``jit_prefill_step``."""
    return text.split("(", 1)[0]


CONTROL_FLOW = ("while", "conditional", "call")


def _owner(mods: List[Tuple[str, Interval]], starts: List[int], t: int):
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and mods[i][1][0] <= t < mods[i][1][1]:
        return mods[i][0]
    return None


def reduce(pd, top: int = 10) -> Reduced:
    spans: List[Tuple[str, Interval]] = []
    window: Optional[Interval] = None
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for name, s, e, _ in _events(line):
                if name == WINDOW:
                    window = (s, e) if window is None else \
                        (min(window[0], s), max(window[1], e))
                elif name.startswith(PREFIX):
                    spans.append((name[len(PREFIX):], (s, e)))

    devices = device_planes(pd)
    ops_by_dev: List[List[Tuple[str, Interval]]] = []
    mods: List[Tuple[str, Interval]] = []
    for plane in devices:
        lines = {l.name: l for l in plane.lines}
        mine = sorted((program_name(n), (s, e))
                      for n, s, e, _ in _events(lines["XLA Modules"])) \
            if "XLA Modules" in lines else []
        mine.sort(key=lambda m: m[1][0])
        starts = [iv[0] for _, iv in mine]
        ops = []
        for name, s, e, _ in _events(lines["XLA Ops"]):
            op = op_name(name)
            owner = _owner(mine, starts, s)
            ops.append((f"{owner}/{op}" if owner else op, (s, e),
                        op.split(".")[0] in CONTROL_FLOW))
        ops_by_dev.append(ops)
        mods.extend(mine)
    if window is None:
        every = [iv for ops in ops_by_dev for _, iv, _ in ops]
        window = (min(s for s, _ in every), max(e for _, e in every)) \
            if every else (0, 0)

    busy_ns, op_time = 0, collections.Counter()
    gaps: List[Tuple[str, float]] = []
    for ops in ops_by_dev:
        clipped = [(n, c, flow) for n, iv, flow in ops
                   if (c := _clip(iv, window))]
        for n, (s, e), flow in clipped:
            if not flow:
                op_time[n] += (e - s) / 1e9
        merged = _merge([c for _, c, _ in clipped])
        busy_ns += sum(e - s for s, e in merged)
        edges = [window[0]] + [x for iv in merged for x in iv] + [window[1]]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                label = max(spans, key=lambda sp: _overlap(sp[1], (s, e)),
                            default=("none", (0, 0)))
                name = label[0] if _overlap(label[1], (s, e)) else "none"
                gaps.append((name, (e - s) / 1e9))
    n = max(1, len(devices))
    programs: Dict[str, List[Tuple[float, float]]] = \
        collections.defaultdict(list)
    for name, iv in sorted(mods, key=lambda m: m[1][0]):
        if iv[0] >= window[0] and iv[1] <= window[1]:
            programs[name].append(((iv[0] - window[0]) / 1e9,
                                   (iv[1] - iv[0]) / 1e9))
    span_s = collections.Counter()
    for name, iv in spans:
        c = _clip(iv, window)
        if c:
            span_s[name] += (c[1] - c[0]) / 1e9
    return Reduced(
        window_s=(window[1] - window[0]) / 1e9, busy_s=busy_ns / n / 1e9,
        n_devices=len(devices), programs=dict(programs),
        top_ops=op_time.most_common(top),
        idle_gaps=sorted(gaps, key=lambda g: -g[1])[:top],
        spans=dict(span_s))


def program_runs(red: Reduced, stem: str) -> List[Tuple[float, float]]:
    """(start, device seconds) of each run of the programs whose name
    contains ``stem`` (e.g. ``prefill_step``); start is seconds after the
    window opened."""
    return sorted(r for name, runs in red.programs.items() if stem in name
                  for r in runs)
