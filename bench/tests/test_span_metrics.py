"""The readers of the program's spans (``bench/spans.py``,
``step_host_ms``, ``admit_host_ms``) on synthetic spans, and
``span_report.py`` end to end on the CPU at a tiny size."""
from types import SimpleNamespace

import pytest

from bench import run as R
from bench import span_report
from bench import spans as S

from .conftest import BENCH


def _round(t, admit=False):
    """One runtime.step from ``t`` (ms): 1 ms of admission (with a 2 ms
    prefill wait and a 0.5 ms first-token wait when ``admit``), 0.5 ms of
    dispatch, a 20 ms decode wait, 1 ms of accounting."""
    ms = 1e-3
    out, c = [], t
    if admit:
        a0 = c
        out += [(c + 0.2 * ms, c + 2.2 * ms, "session.prefill.wait",
                 "session.admit", None),
                (c + 2.4 * ms, c + 2.9 * ms, "session.first_token.wait",
                 "session.admit", None)]
        c += 3.5 * ms
        out += [(a0, c, "session.admit", "sched.admit", 5)]
    out += [(t, c + 1 * ms, "sched.admit", "runtime.step", None)]
    c += 1 * ms
    out += [(c, c + 0.5 * ms, "session.dispatch", "runtime.step", None),
            (c + 0.5 * ms, c + 20.5 * ms, "session.decode.wait",
             "runtime.step", None),
            (c + 20.5 * ms, c + 21.5 * ms, "session.accounting",
             "runtime.step", None)]
    end = c + 21.6 * ms
    out += [(t, end, "runtime.step", "", None)]
    return out, end


def _ctx(spans):
    return SimpleNamespace(spans=spans)


def _window():
    spans, t = [], 0.0
    for i in range(5):
        mine, t = _round(t, admit=i % 2 == 0)
        spans += mine
        t += 0.004
    return spans


def test_step_and_admit_host_time():
    spans = _window()
    step = R.load_reader("step_host_ms", BENCH)(_ctx(spans))
    admit = R.load_reader("admit_host_ms", BENCH)(_ctx(spans))
    # plain rounds: 1 + 0.5 + 1 + 0.1 ms; admitting ones add 3.5 - 2.5
    assert step == pytest.approx(2.6 + 1.0, abs=1e-6)
    assert admit == pytest.approx(1.0, abs=1e-6)


@pytest.mark.parametrize("name", ["step_host_ms", "admit_host_ms"])
def test_readers_silent_without_spans(name):
    read = R.load_reader(name, BENCH)
    assert read(_ctx([])) is None
    assert read(SimpleNamespace()) is None       # a harness without spans


def test_tree_nests_and_sums_siblings():
    spans, _ = _round(0.0, admit=True)
    extra = [(0.0260, 0.0261, "session.accounting", "runtime.step", None)]
    root = [sp for sp in spans if sp[2] == "runtime.step"][0]
    text = S.tree(spans + extra, root)
    assert text.startswith("runtime.step 26.100 [sched.admit 4.500 "
                           "[session.admit 3.500 [session.prefill.wait "
                           "2.000, session.first_token.wait 0.500]], ")
    assert "session.accounting x2 1.100" in text


def test_span_report_runs_on_the_cpu(tiny_bench):
    root, cell = tiny_bench
    sink, load = R.EventSink, R.trace_reduce.load
    out = span_report.run(cell, 2**31 + 5, 2.0, False, bench=root,
                          require_chip=False)
    assert all(out["per_layer"][n] > 0
               for n in ("step_host_ms", "admit_host_ms"))
    sp = out["spans"]
    assert sp["steps"] > 0 and sp["longest_tree"].startswith("runtime.step")
    assert 0.0 < sp["children_cover"] <= 1.0
    assert "session.decode.wait" in sp["child_ms_per_step"]
    assert out["span_cost"]["us_per_span_off"] > 0
    # the harness's own sink and trace loader are put back
    assert R.EventSink is sink and R.trace_reduce.load is load
