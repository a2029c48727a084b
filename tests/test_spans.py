"""Program spans (``telemetry.span``): nesting, the off path, the span
tree of a serving round, the tracer's ring left to the program's other
events, and the spans on the profiler's own trace.

A ``runtime.step`` round holds the spans below; every blocking
device->host read sits in exactly one span whose name ends in ``.wait``
(prefill, first token, decode), and no other span carries that suffix.
"""
import threading
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core.speculative import SpecDecodeSpec
from repro.models import init_params
from repro.models.layers import RuntimeCfg
from repro.runtime import telemetry
from repro.runtime.serve_loop import Request
from repro.runtime.server import PartitionSpec, ServingRuntime, ServingSpec

RT = RuntimeCfg(ssm_chunk=16)
MAX_LEN = 64
WAITS = {"session.prefill.wait", "session.first_token.wait",
         "session.decode.wait"}


class _Sink:
    def __init__(self):
        self.events = []

    def on_event(self, ev):
        self.events.append(ev)

    def spans(self):
        return [e for e in self.events if e.kind == "span"]


def _sink(tr):
    sink = _Sink()
    tr.add_sink(sink)
    return sink


def _spans(sink):
    return [(e.meta["name"], e.meta["parent"]) for e in sink.spans()]


# ---------------------------------------------------------------------------
# Mechanics
# ---------------------------------------------------------------------------

def test_nesting_parents_and_self_time():
    tr = telemetry.Tracer()
    sink = _sink(tr)
    with telemetry.span(tr, "outer", step=3, uid=9):
        with telemetry.span(tr, "inner.wait"):
            pass
        with telemetry.span(tr, "inner2"):
            with telemetry.span(tr, "leaf"):
                pass
    evs = {e.meta["name"]: e for e in sink.spans()}
    assert _spans(sink) == [("inner.wait", "outer"), ("leaf", "inner2"),
                          ("inner2", "outer"), ("outer", "")]
    outer = evs["outer"]
    assert outer.step == 3 and outer.meta["uid"] == 9
    assert evs["leaf"].step == -1 and "uid" not in evs["leaf"].meta
    # t is the end; the children lie inside the parent in time
    for name in ("inner.wait", "inner2"):
        child = evs[name]
        assert outer.t - outer.wall_s <= child.t - child.wall_s
        assert child.t <= outer.t
    self_s = outer.wall_s - evs["inner.wait"].wall_s - evs["inner2"].wall_s
    assert 0 <= self_s <= outer.wall_s
    assert [e.meta["name"] for e in sink.events] == \
        ["inner.wait", "leaf", "inner2", "outer"]
    # counted, but kept out of the ring the program's sample views read
    assert tr.counts()["span"] == 4
    assert tr.events() == []


def test_span_stack_is_per_thread():
    tr = telemetry.Tracer()
    sink = _sink(tr)
    barrier = threading.Barrier(4)

    def work(k):
        barrier.wait()
        for _ in range(200):
            with telemetry.span(tr, f"t{k}.outer"):
                with telemetry.span(tr, f"t{k}.inner"):
                    pass

    threads = [threading.Thread(target=work, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    spans = _spans(sink)
    assert len(spans) == 4 * 2 * 200
    for name, parent in spans:
        k = name.split(".")[0]
        assert parent == ("" if name.endswith("outer") else f"{k}.outer")


def test_no_tracer_records_nothing_and_raises_nothing():
    with telemetry.span(None, "a", step=1, uid=2):
        with telemetry.span(None, "b.wait"):
            pass
    with pytest.raises(ValueError):
        with telemetry.span(None, "c"):
            raise ValueError("propagates")
    # the stack unwound: a fresh span is at the top again
    tr = telemetry.Tracer()
    sink = _sink(tr)
    with telemetry.span(tr, "d"):
        pass
    assert _spans(sink) == [("d", "")]


# ---------------------------------------------------------------------------
# A serving round
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def model():
    cfg = get_reduced("llama3-8b")
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def _runtime(model, paged=True, speculative=None, capacity=4096,
             requests=1):
    """A one-partition runtime with ``requests`` queued (uids from 7)
    and a sink on its tracer."""
    cfg, params = model
    spec = ServingSpec(partitions=(PartitionSpec(policy="bf16:dense:jnp"),),
                       batch_slots=2, max_len=MAX_LEN, paged=paged,
                       page_size=16, speculative=speculative)
    rt = ServingRuntime(params, cfg, spec, rt=RT, tracer_capacity=capacity)
    rt.add_tenant("t0", partition=0)
    for uid in range(7, 7 + requests):
        rt.submit("t0", Request(uid=uid, max_new=4,
                                prompt=np.arange(1, 6, dtype=np.int32)))
    return rt, _sink(rt.tracers[0])


@pytest.mark.parametrize("paged", [True, False])
def test_round_records_the_span_tree(model, paged):
    rt, sink = _runtime(model, paged=paged)
    rt.step()                         # one admission and one decode
    spans = _spans(sink)
    expect = [("session.prefill.wait", "session.admit"),
              ("session.first_token.wait", "session.admit"),
              ("session.admit", "sched.admit"),
              ("sched.admit", "runtime.step")]
    expect += [("session.pages", "session.dispatch")] if paged else []
    expect += [("session.dispatch", "runtime.step"),
               ("session.decode.wait", "runtime.step"),
               ("session.accounting", "runtime.step"),
               ("sched.finish", "runtime.step"),
               ("runtime.step", "")]
    assert spans == expect
    waits = [n for n, _ in spans if n.endswith(".wait")]
    assert sorted(waits) == sorted(WAITS)          # one per blocking read
    evs = {e.meta["name"]: e for e in sink.spans()}
    assert evs["runtime.step"].step == 0
    assert evs["session.admit"].meta["uid"] == 7
    # the prefill wait is the interval of the prefill event
    pre, wait = rt.tracers[0].events("prefill")[0], \
        evs["session.prefill.wait"]
    assert pre.wall_s <= wait.wall_s
    rt.step()                         # a decode-only round: no admission
    second = _spans(sink)[len(spans):]
    assert [n for n, _ in second if n.endswith(".wait")] == \
        ["session.decode.wait"]
    assert ("session.admit", "sched.admit") not in second


def test_decode_event_meta_has_no_dispatch_to_ready(model):
    rt, _ = _runtime(model)
    rt.step()
    (dec,) = rt.tracers[0].events("decode")
    assert dec.meta == {"n_active": 1, "kv_inplace": 1}


def test_speculative_round_waits_once(model):
    rt, sink = _runtime(model, speculative=SpecDecodeSpec(k=2))
    rt.step()
    rt.step()
    spans = _spans(sink)
    second = spans[spans.index(("runtime.step", "")) + 1:]
    assert [n for n, _ in second if n.endswith(".wait")] == \
        ["session.decode.wait"]
    assert ("session.accounting", "runtime.step") in second
    assert rt.tracers[0].events("decode")[-1].meta["spec_k"] == 2


def _ring_run(model, capacity):
    """Serve four requests to the end on a ring of ``capacity``; the
    ring's events (without their times) and every event the sink saw."""
    rt, sink = _runtime(model, capacity=capacity, requests=4)
    while rt.pending() or rt.n_active:
        rt.step()
    ring = rt.tracers[0].events()
    return rt.tracers[0], [(e.kind, e.step, e.tenant, e.meta.get("uid"))
                           for e in ring], sink


@pytest.mark.filterwarnings("ignore:Tracer.capacity=16. began evicting")
def test_spans_leave_the_ring_to_the_program(model, monkeypatch):
    """The ring is the sample window the program's control loops read
    (quota, placement, attainment): spans reach the sinks but not the
    ring, so a run that fills it retains the same events as one that
    records no spans."""
    tr, with_spans, sink = _ring_run(model, capacity=16)
    assert len(sink.spans()) > 16 and tr.counts()["span"] == \
        len(sink.spans())
    assert "span" not in tr.dropped() and tr.dropped()
    assert tr.events() == \
        [e for e in sink.events if e.kind != "span"][-16:]

    ingest = telemetry.Tracer._ingest

    def no_spans(self, ev, ring=True):
        if ev.kind != "span":
            ingest(self, ev, ring)

    monkeypatch.setattr(telemetry.Tracer, "_ingest", no_spans)
    _, without, bare = _ring_run(model, capacity=16)
    assert not bare.spans()
    assert with_spans == without
    assert {k for k, *_ in with_spans} >= {"decode", "request"}


def test_spans_land_nested_on_the_profiler_trace(model, tmp_path):
    from jax.profiler import ProfileData
    rt, _ = _runtime(model)
    rt.step()                         # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        rt.step()
    (path,) = Path(tmp_path).rglob("*.xplane.pb")
    events = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(telemetry.SPAN_PREFIX):
                    events[e.name] = (e.start_ns, e.start_ns + e.duration_ns)
    step = events["repro.runtime.step"]
    wait = events["repro.session.decode.wait"]
    assert step[0] <= wait[0] and wait[1] <= step[1]
