"""Idle gaps named by the program's spans, on a small trace recorded on
one TPU v5e.

``data/small_tpu_spans.xplane.pb`` was written by
``record_span_trace.py`` on the chip: three ``bench.step`` rounds, each a
``runtime.step`` program span holding a small program
(``session.dispatch``, ``session.decode.wait``), 10 ms of host-only work
(``session.accounting``) and a matmul program, then a 20 ms
``bench.idle_wait``; all in one ``bench.trace_window``. On that machine
the device plane reads about 1 ms early against the host plane: each
program starts about 1 ms before the span that dispatched it.
"""
import pytest

from bench import trace_reduce as T
from bench import trace_spans as TS

from .conftest import DATA


@pytest.fixture(scope="module")
def pd():
    return T.load(DATA / "small_tpu_spans.xplane.pb")


def test_host_work_gap_names_the_program_span(pd):
    idle = TS.attribute(pd)
    gaps = idle.idle_gaps[:6]
    host = [s for name, s in gaps if name == "step/session.accounting"]
    waits = [s for name, s in gaps if name.startswith("idle_wait")]
    assert len(host) == 3 and all(0.009 < s < 0.014 for s in host)
    assert len(waits) == 3 and all(0.019 < s < 0.026 for s in waits)
    assert idle.idle_by_span["session.accounting"] == \
        pytest.approx(0.032, abs=0.005)


def test_idle_by_span_within_window_idle(pd):
    idle = TS.attribute(pd)
    red = T.reduce(pd)
    assert idle.window_s == pytest.approx(red.window_s)
    assert idle.idle_s == pytest.approx(red.window_s - red.busy_s)
    assert sum(idle.idle_by_span.values()) <= idle.idle_s + 1e-9
    assert sum(idle.idle_by_span.values()) <= \
        idle.idle_by_harness["step"] + 1e-9
    assert sum(idle.idle_by_harness.values()) <= idle.idle_s + 1e-9
    # the harness-only reduction keeps its own labels
    assert {name for name, _ in red.idle_gaps} <= {"step", "idle_wait"}


def test_without_program_spans_labels_match_trace_reduce():
    pd = T.load(DATA / "small_tpu.xplane.pb")
    idle, red = TS.attribute(pd), T.reduce(pd)
    assert idle.idle_gaps == red.idle_gaps
    assert idle.idle_by_span == {}


def test_innermost_takes_the_shortest_covering_span():
    segs = TS.innermost([("step", (0, 100)), ("wait", (10, 40)),
                         ("acct", (60, 90))])
    assert segs == [(0, 10, "step"), (10, 40, "wait"), (40, 60, "step"),
                    (60, 90, "acct"), (90, 100, "step")]
    cover = TS.Cover([("step", (0, 100)), ("wait", (10, 40))])
    assert cover.of((5, 50)) == {"step": 15, "wait": 30}
    assert cover.of((200, 300)) == {}


def test_device_lead_bounds_the_clock_offset(pd):
    lo, hi = TS.device_lead(pd, program="jit__lambda")
    assert 0.0005 < lo <= hi < 0.003
    assert TS.device_lead(T.load(DATA / "small_tpu.xplane.pb")) is None
