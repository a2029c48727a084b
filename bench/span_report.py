#!/usr/bin/env python3
"""Where the host's time goes inside ``runtime.step()``: one cell, one chip.

    python3 bench/span_report.py --workload <cell> --seed <n> --seconds <s> \\
        [--trace 0|1]

Runs a cell through ``bench/run.py``'s own ``measure`` (the same weights,
runtime, warm-up, traffic and window), with its event sink taught to keep
the program's ``span`` events (``repro.runtime.telemetry.Span``) and, in a
traced run, the profiler trace kept for ``bench/trace_spans.py``. Reports
on stderr, then as one JSON line on stdout:

* ``step_host_ms`` / ``admit_host_ms`` (``bench/metrics``);
* the span tree of the window's longest ``runtime.step``, and how much of
  the median step's host time its child spans cover;
* with ``--trace 1``, the device's idle time named by the program span it
  waited for, and bounds on how far the device plane's clock leads the
  host plane's;
* what one span costs on this host, with the profiler off and on.

A stopgap: ``bench/run.py`` and ``bench/trace_reduce.py`` do not read the
program's spans yet (PERF.md, Open questions). The change that teaches
them to deletes this file and the gap pass of ``bench/trace_spans.py``.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parent.parent
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import numpy as np  # noqa: E402

from bench import model as bmodel  # noqa: E402
from bench import run as R  # noqa: E402
from bench import spans as S  # noqa: E402
from bench import trace_reduce, trace_spans  # noqa: E402
from bench import traffic as btraffic  # noqa: E402

SPAN_METRICS = ("step_host_ms", "admit_host_ms")
COST_SPANS = 20000


class SpanSink(R.EventSink):
    """The harness's prefill and decode events, and the program's spans
    as ``bench/spans.py`` takes them."""

    def __init__(self):
        super().__init__()
        self.spans = []

    def on_event(self, ev):
        if ev.kind == "span":
            self.spans.append((ev.t - ev.wall_s, ev.t, ev.meta.get("name"),
                               ev.meta.get("parent", ""),
                               ev.meta.get("uid")))
        else:
            super().on_event(ev)


def span_cost(n: int = COST_SPANS) -> dict:
    """Microseconds per span (nested in pairs, recorded on a tracer with
    one sink), with the profiler off and on."""
    import jax
    from repro.runtime import telemetry
    tr = telemetry.Tracer()
    tr.add_sink(SpanSink())

    def pairs():
        t = time.perf_counter()
        for i in range(n // 2):
            with telemetry.span(tr, "cost.outer", step=i):
                with telemetry.span(tr, "cost.inner.wait"):
                    pass
        return (time.perf_counter() - t) / n * 1e6

    off = pairs()
    d = tempfile.mkdtemp(prefix="span_cost_")
    try:
        jax.profiler.start_trace(d)
        on = pairs()
        jax.profiler.stop_trace()
    finally:
        shutil.rmtree(d, ignore_errors=True)
    return {"us_per_span_off": off, "us_per_span_on": on}


def run(cell: dict, seed: int, seconds: float, trace: bool,
        bench: Path = R.BENCH, require_chip: bool = True) -> dict:
    import jax
    devs = R.check_device(int(cell.get("chips", 1)))[0] if require_chip \
        else jax.devices()
    conf = bmodel.load_config(cell["config"], bench)
    traffic = btraffic.load_traffic(cell["traffic"], bench)
    R.log(f"device {devs[0].device_kind} x{len(devs)}; cell {cell['name']}")
    traces, load = [], trace_reduce.load

    def keep(path):
        traces.append(load(path))
        return traces[-1]

    R.EventSink, trace_reduce.load = SpanSink, keep
    try:
        m = R.measure(conf, traffic, seed, seconds, trace,
                      R.cell_devices(cell, devs), R.CompileCounter())
    finally:
        R.EventSink, trace_reduce.load = SpanSink.__base__, load
    spans = [sp for sp in m.sink.spans if m.w0 <= sp[0] and sp[1] <= m.w1]
    ctx = SimpleNamespace(spans=spans)
    out = {"workload": cell["name"], "seed": seed, "trace": int(trace),
           "per_layer": {n: R.load_reader(n, bench)(ctx)
                         for n in SPAN_METRICS},
           "spans": step_spans(spans, m.w0)}
    R.log(f"per-layer: {out['per_layer']}")
    if traces:
        out["idle"] = idle_summary(trace_spans.attribute(traces[-1]))
        out["idle"]["trace_at_s"] = m.trace_iv[0] - m.w0
        out["device_lead_s"] = trace_spans.device_lead(traces[-1])
        R.log(f"trace: the device plane reads {out['device_lead_s']} s "
              f"ahead of the host plane (lower, upper bound)")
    out["span_cost"] = span_cost()
    R.log(f"span cost: {out['span_cost']}")
    return out


def step_spans(spans, w0: float) -> dict:
    """The longest ``runtime.step`` (its start, seconds after ``w0``, and
    its tree); the median step's host time and how much of it the step's
    child spans cover; each child's mean duration per step; spans per
    step."""
    idx = S.Spans(spans)
    rounds = idx.named("runtime.step")
    if not rounds:
        return {}
    longest = max(rounds, key=lambda sp: sp[1] - sp[0])
    host, bare, child_s, n_spans = [], [], {}, 0
    for r in rounds:
        inside = idx.inside(r)
        kids = [c for c in inside if c[3] == "runtime.step"]
        host.append(idx.host_s(r))
        bare.append(r[1] - r[0] - sum(c[1] - c[0] for c in kids))
        for c in kids:
            child_s[c[2]] = child_s.get(c[2], 0.0) + c[1] - c[0]
        n_spans += 1 + len(inside)
    out = {
        "steps": len(rounds),
        "spans_per_step": n_spans / len(rounds),
        "host_ms_median": float(np.median(host)) * 1e3,
        "outside_children_ms_median": float(np.median(bare)) * 1e3,
        "children_cover": 1.0 - float(np.median(bare) / np.median(host)),
        "child_ms_per_step": {k: v / len(rounds) * 1e3
                              for k, v in child_s.items()},
        "longest_ms": (longest[1] - longest[0]) * 1e3,
        "longest_at_s": longest[0] - w0,
        "longest_tree": S.tree(spans, longest)}
    R.log(f"spans of the longest runtime.step (ms): {out['longest_tree']}")
    R.log(f"host time per runtime.step (waits excluded): median "
          f"{out['host_ms_median']:.3f} ms; outside its child spans median "
          f"{out['outside_children_ms_median']:.3f} ms; "
          f"{out['spans_per_step']:.1f} spans a step over {len(rounds)}")
    return out


def idle_summary(idle: trace_spans.SpanIdle) -> dict:
    in_step = idle.idle_by_harness.get("step", 0.0)
    named = sum(idle.idle_by_span.values())
    out = {"window_s": idle.window_s, "idle_s": idle.idle_s,
           "idle_in_step_s": in_step,
           "named_share_of_step_idle": named / in_step if in_step else None,
           "idle_by_span": idle.idle_by_span,
           "idle_by_harness": idle.idle_by_harness,
           "idle_gaps": idle.idle_gaps}
    R.log(f"trace: idle {idle.idle_s:.4f} s of {idle.window_s:.4f} s; by "
          f"harness span {idle.idle_by_harness}; by program span "
          f"{idle.idle_by_span}; longest gaps {idle.idle_gaps}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = R.load_cell(args.workload)
        R.log(f"compile cache {R.setup_jax()}")
        out = run(cell, args.seed, args.seconds, bool(args.trace))
    except R.Failure as e:
        R.log(f"FAILED: {e}")
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
