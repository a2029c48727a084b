#!/usr/bin/env python3
"""Serving benchmark harness: one cell, one process, one chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell comes from ``BENCHMARK.json`` at the root of the checkout; it
names a configuration (``bench/configs/<name>.json``) and a traffic mix
(``bench/traffic/<name>.json``). In order, the run:

1. checks that JAX sees a TPU with as many chips as the cell asks for, and
   looks its ``device_kind`` up in ``bench/peaks.json``;
2. makes the weights from ``--seed`` on the device and builds the
   program's ``ServingRuntime`` with a paged KV cache: the partitions, the
   placement and the migration that the configuration's ``serving`` block
   gives (one partition by default), and the traffic's tenants;
3. warms up, on every partition, one prefill per prompt length of the mix
   and the decode step;
4. offers the mix open-loop for its pre-roll, then measures for
   ``--seconds``: one loop submits every request that is due, calls
   ``runtime.step()`` and stamps each new output token on the host clock;
5. reads the fullest device's peak memory, frees the runtime and its weights,
   makes the published weights again from the seed, and checks a sample
   of the finished requests against the plain float32 reference
   (``bench/references/<reference>.py``): the gaps by which served
   tokens' logits lie below the reference's best at their positions must
   be within the configuration's limits.

With ``--trace 0`` the result line holds the cell's end-to-end metrics;
with ``--trace 1`` a profiler trace of a few seconds of the window is taken
and the line holds its per-layer metrics (one reader each in
``bench/metrics/<name>.py``) and a breakdown of device time and idle gaps.
The last line on stdout is one JSON object; diagnostics go to stderr, and
the last lines there are the compared numbers with their limits.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from collections import Counter, deque  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402
from typing import List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import numpy as np  # noqa: E402

from bench import archs  # noqa: E402
from bench import model as bmodel  # noqa: E402
from bench import traffic as btraffic  # noqa: E402
from bench import trace_reduce  # noqa: E402
from bench.peaks import peaks_for  # noqa: E402

TRACE_SECONDS = 4.0       # traced run: profile the window's last seconds
SAMPLE_TOKENS = 300       # compared tokens the correctness sample reaches
SAMPLE_MIN = 6            # ... over at least this many requests
SAMPLE_MAX = 10           # ... and at most this many
PER_REQUEST = 64          # positions compared in one request, at most
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


class Failure(RuntimeError):
    """The run cannot produce a result (no chip, bad cell, ...)."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# -- the cell ------------------------------------------------------------------

def load_cell(name: str, root: Path = ROOT) -> dict:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return dict(cell, end_to_end=spec["end_to_end"],
                        per_layer=spec["per_layer"])
    raise Failure(f"no workload {name!r} in BENCHMARK.json")


def metrics_of(cell: dict, kind: str) -> List[dict]:
    """The cell's metrics of ``kind`` (end_to_end or per_layer)."""
    return [m for m in cell[kind]
            if cell["name"] in m.get("workloads", [cell["name"]])]


def load_reader(name: str, bench: Path = BENCH):
    path = bench / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_reference(conf: dict, bench: Path = BENCH):
    path = bench / "references" / f"{conf['reference']}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_ref_{conf['reference']}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- the device ----------------------------------------------------------------

def check_device(chips: int):
    """(devices, peaks); no TPU, too few chips or an unknown kind raise."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Failure(f"JAX found no TPU (platform {devs[0].platform!r}); "
                      f"this benchmark measures on the chip only")
    if len(devs) < chips:
        raise Failure(f"the cell needs {chips} chips, JAX found {len(devs)}")
    try:
        peaks = peaks_for(devs[0].device_kind)
    except KeyError as e:
        raise Failure(str(e)) from None
    return devs, peaks


def cell_devices(cell: dict, devs) -> list:
    """The devices a cell runs on: the first ``chips`` of ``devs``."""
    return list(devs[:int(cell.get("chips", 1))])


class CompileCounter:
    """Timestamps of JAX compiles and persistent-cache loads."""

    def __init__(self):
        self.times: List[float] = []

    def __call__(self, event, duration, **kw):
        if event in COMPILE_EVENTS:
            self.times.append(time.perf_counter())

    def between(self, a: float, b: float) -> int:
        return sum(a <= t < b for t in self.times)


class EventSink:
    """Every prefill and decode event of the program's tracers."""

    def __init__(self):
        self.prefill, self.decode = [], []

    def on_event(self, ev):
        if ev.kind == "prefill":
            self.prefill.append((ev.t - ev.wall_s, ev.t, ev.m,
                                 ev.meta.get("uid")))
        elif ev.kind == "decode":
            self.decode.append((ev.t - ev.wall_s, ev.t,
                                ev.meta.get("n_active", 0)))


class GcPauses:
    """(start, end) of every garbage collection, from ``gc.callbacks``."""

    def __init__(self):
        self.spans: List[tuple] = []
        self._t = 0.0

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.spans.append((self._t, time.perf_counter()))


def log_host_and_kv(conf: dict, steps, pauses: GcPauses, w0: float,
                    w1: float) -> None:
    """Where the host could stall the window (the longest step, the
    longest gap between steps, garbage collections) and the KV cache the
    window's decode steps held, against the reserved pool."""
    from bench import work
    inside = [s for s in steps if w0 <= s[0] and s[1] <= w1]
    gc_in = [e - s for s, e in pauses.spans if w0 <= s < w1]
    if inside:
        longest = max(e - s for s, e, _, _ in inside)
        gap = max((b[0] - a[1] for a, b in zip(inside, inside[1:])),
                  default=0.0)
        log(f"host: longest step {longest * 1e3:.1f} ms, longest gap "
            f"between steps {gap * 1e3:.1f} ms; {len(gc_in)} collections "
            f"in the window, {sum(gc_in) * 1e3:.1f} ms in all, longest "
            f"{max(gc_in, default=0.0) * 1e3:.1f} ms")
    live = [sum(p + 1 for p in dec) for _, _, dec, _ in inside if dec]
    if live:
        per_tok = work.kv_bytes_per_token(conf)
        serving = conf["serving"]
        reserved = serving["batch_slots"] * serving["max_len"] * per_tok
        log(f"KV in use over the window's decode steps: mean "
            f"{np.mean(live):.0f} positions ({np.mean(live) * per_tok / 1e9:.2f}"
            f" GB), max {max(live)} ({max(live) * per_tok / 1e9:.2f} GB), "
            f"of {reserved / 1e9:.2f} GB reserved")


# -- serving -------------------------------------------------------------------

@dataclasses.dataclass
class Rec:
    """One request as the client sees it."""
    due: float                       # absolute host time it was due
    req: object                      # the program's Request
    submit_t: float = 0.0
    stamps: List[float] = dataclasses.field(default_factory=list)
    prefill_start: Optional[float] = None


def build_runtime(params, cfg, conf: dict, seed: int, devs, tenants):
    """The serving block's partitions (``partitions``: the program's
    ``PartitionSpec`` fields, the block's ``policy`` where a partition
    names none; one partition by default), one to a device where ``devs``
    (the cell's) has one for each, else logical ones on the first; its
    ``placement`` and ``migration`` where it gives them. Each of
    ``tenants`` is placed by the runtime and may hold every slot of its
    partition: the scheduler's default quota (4 slots for a
    latency-sensitive tenant) would leave most of the batch idle, so a
    tenant's policy carries a stream budget of the partition's slots. A
    partition that no tenant lands on gets an idle tenant ``warm<i>`` for
    the warm-up."""
    from repro.core import execution as ex
    from repro.models.layers import RuntimeCfg
    from repro.runtime.server import (
        ServingRuntime, ServingSpec, make_partitions)
    s = conf["serving"]
    spec = ServingSpec.from_dict(dict(
        {k: s[k] for k in ("placement", "migration") if k in s},
        partitions=[dict({"policy": s["policy"]}, **p)
                    for p in s.get("partitions", [{}])],
        batch_slots=s["batch_slots"], max_len=s["max_len"], paged=True,
        page_size=s["page_size"], seed=bmodel.jax_seed(seed)))
    n = spec.n_partitions
    runtime = ServingRuntime(params, cfg, spec, rt=RuntimeCfg(),
                             tracer_capacity=1 << 20,
                             partitions=make_partitions(n, devs[:n]))
    policy = dataclasses.replace(ex.parse_policy(s["policy"]), streams=max(
        p.batch_slots or spec.batch_slots for p in spec.partitions))
    for tenant in tenants:
        runtime.add_tenant(tenant, policy=policy)
    for i in set(range(n)) - set(runtime.tenant_partition.values()):
        runtime.add_tenant(f"warm{i}", partition=i, policy=policy)
    return runtime


def warm_up(runtime, traffic: dict, vocab: int, seed: int) -> None:
    """On every partition, through the first tenant placed there: one
    request per prompt length of the mix, then the shortest length until
    every slot holds one. Compiles each prefill and prompt write, the
    decode step, and the slot bookkeeping at every slot index."""
    from repro.runtime.serve_loop import Request
    rng = np.random.default_rng(seed + 1)
    first = {}
    for tenant, i in runtime.tenant_partition.items():
        first.setdefault(i, tenant)
    uid = -1
    for i, sess in enumerate(runtime.sessions):
        lens = list(traffic["prompt"]["lengths"])
        lens += [min(lens)] * max(0, sess.batch_slots - len(lens))
        for lp in lens:
            runtime.submit(first[i], Request(
                uid=uid, prompt=rng.integers(0, vocab, lp, dtype=np.int32),
                max_new=3))
            uid -= 1
    runtime.drain()


def serve(runtime, arrivals, t0: float, w0: float, w1: float,
          trace_dir: Optional[str] = None):
    """Offer ``arrivals`` open-loop from ``t0`` until ``w1``.

    Returns (recs, step log, trace host interval or None, requests queued
    at the window's open and close). The step log has one row per
    ``runtime.step()``: (start, end, positions decoded, prompt lengths
    admitted)."""
    import jax
    from repro.runtime.serve_loop import Request
    pending = deque(arrivals)
    live: dict = {}
    recs: List[Rec] = []
    steps = []
    tracing, trace_iv, window_span = None, None, None
    queued = [None, None]
    while True:
        now = time.perf_counter()
        if now >= w1:
            break
        if queued[0] is None and now >= w0:
            queued[0] = runtime.pending()
        if trace_dir is not None and tracing is None \
                and now >= max(w0, w1 - TRACE_SECONDS):
            jax.profiler.start_trace(trace_dir)
            window_span = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
            window_span.__enter__()
            tracing = time.perf_counter()
        if pending and t0 + pending[0].due_s <= now:
            with jax.profiler.TraceAnnotation("bench.submit"):
                while pending and t0 + pending[0].due_s <= now:
                    a = pending.popleft()
                    rec = Rec(due=t0 + a.due_s, req=Request(
                        uid=a.uid, prompt=a.prompt, max_new=a.max_new))
                    runtime.submit(a.tenant, rec.req)
                    rec.submit_t = time.perf_counter()
                    recs.append(rec)
                    live[a.uid] = rec
            continue
        if runtime.pending() or runtime.n_active:
            ts = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.step"):
                runtime.step()
            te = time.perf_counter()
            decoded, admitted = [], []
            for uid in list(live):
                rec = live[uid]
                out, lp = rec.req.out, len(rec.req.prompt)
                if len(out) > len(rec.stamps):
                    if not rec.stamps:
                        rec.stamps.append(rec.req.admit_t)
                        admitted.append(lp)
                    rec.stamps.extend([te] * (len(out) - len(rec.stamps)))
                    if len(out) > 1:
                        decoded.append(lp + len(out) - 2)
                if rec.req.done:
                    del live[uid]
            steps.append((ts, te, decoded, admitted))
            continue
        nxt = t0 + pending[0].due_s if pending else w1
        with jax.profiler.TraceAnnotation("bench.idle_wait"):
            time.sleep(max(0.0, min(nxt, w1) - time.perf_counter()))
    if tracing is not None:
        # stopping writes the trace out, which takes seconds: only after
        # the window has closed
        window_span.__exit__(None, None, None)
        trace_iv = (tracing, time.perf_counter())
        jax.profiler.stop_trace()
    queued[1] = runtime.pending()
    return recs, steps, trace_iv, tuple(queued)


# -- end-to-end metrics ----------------------------------------------------------

def percentile(xs, q: float) -> float:
    return float(np.percentile(np.asarray(xs, float), q))


def end_to_end(recs: List[Rec], w0: float, w1: float) -> dict:
    due = [r for r in recs if w0 <= r.due < w1]
    ttft = [(r.stamps[0] if r.stamps and r.stamps[0] <= w1 else w1) - r.due
            for r in due]
    itl, tokens = [], 0
    for r in recs:
        inside = [t for t in r.stamps if w0 <= t <= w1]
        tokens += len(inside)
        itl.extend(np.diff(inside).tolist())
    return {"ttft_p90_ms": None if not ttft else percentile(ttft, 90) * 1e3,
            "itl_p95_ms": None if not itl else percentile(itl, 95) * 1e3,
            "output_tokens_per_s": tokens / (w1 - w0),
            "n_due": len(due), "n_itl": len(itl), "n_tokens": tokens}


# -- correctness -----------------------------------------------------------------

def pick_sample(finished: List[Rec], seed: int) -> List[Rec]:
    """The request with the most served tokens, then others drawn from the
    seed, those that were live beside it first, until the sample holds
    SAMPLE_MIN requests and SAMPLE_TOKENS compared tokens."""
    if not finished:
        return []
    rng = np.random.default_rng(seed + 2)
    longest = max(finished, key=lambda r: (len(r.req.out), len(r.req.prompt)))
    a, b = longest.stamps[0], longest.stamps[-1]
    rest = [finished[i] for i in rng.permutation(len(finished))
            if finished[i] is not longest]
    rest.sort(key=lambda r: not (r.stamps[0] <= b and a <= r.stamps[-1]))
    sample = [longest]
    for r in rest:
        if len(sample) >= SAMPLE_MAX or (
                len(sample) >= SAMPLE_MIN and sum(
                    min(len(s.req.out), PER_REQUEST) for s in sample)
                >= SAMPLE_TOKENS):
            break
        sample.append(r)
    return sample


def compared_positions(n: int) -> np.ndarray:
    """Of a request's ``n`` served tokens, at most PER_REQUEST evenly
    spaced, the first and the last among them."""
    return np.linspace(0, n - 1, min(n, PER_REQUEST)).astype(int)


def ref_length(traffic: dict) -> int:
    """Fixed reference length: the longest prompt plus the longest output,
    rounded up to 512 (one compile)."""
    n = max(traffic["prompt"]["lengths"]) + traffic["output"]["max"]
    return -(-n // 512) * 512


def reference_inputs(rec: Rec, T: int, n_max: int):
    """(tokens (T,), rows (n_max,), served (n_max,), n) for the reference:
    the prompt and the served tokens but the last, padded to ``T``; the
    rows whose logits predicted each served token, padded to ``n_max``."""
    prompt, served = np.asarray(rec.req.prompt), np.asarray(rec.req.out)
    lp, n = len(prompt), len(served)
    toks = np.zeros((T,), np.int32)
    seq = np.concatenate([prompt, served[:-1]])
    toks[:len(seq)] = seq
    rows = np.full((n_max,), lp + n - 2, np.int32)
    rows[:n] = lp - 1 + np.arange(n)
    tgt = np.zeros((n_max,), np.int32)
    tgt[:n] = served
    return toks, rows, tgt, n


def gap_of(logits, tokens):
    """Reference best logit minus the reference logit of ``tokens``."""
    import jax.numpy as jnp
    return jnp.max(logits, -1) - jnp.take_along_axis(
        logits, jnp.asarray(tokens)[:, None], -1)[:, 0]


def served_gaps(fwd, params, traffic: dict, sample: List[Rec]):
    """Per request: the gaps at its compared positions."""
    T, n_max = ref_length(traffic), traffic["output"]["max"]
    out = []
    for r in sample:
        toks, rows, tgt, n = reference_inputs(r, T, n_max)
        g = np.asarray(gap_of(fwd(params, toks, rows), tgt))
        out.append(g[compared_positions(n)])
    return out


def gap_readings(gaps) -> dict:
    """The numbers a run can compare, from the per-token gaps: the widest
    gap, the mean gap, and the share of served tokens that are not the
    reference's best (gap above 0)."""
    n = sum(len(g) for g in gaps)
    if not n:
        return {"tokens": 0, "logit_gap": float("inf"),
                "mean_gap": float("inf"), "mismatch_share": 1.0}
    return {"tokens": n,
            "logit_gap": max(float(g.max()) for g in gaps),
            "mean_gap": sum(float(g.sum()) for g in gaps) / n,
            "mismatch_share": sum(int((g > 0).sum()) for g in gaps) / n}


def compare(readings: dict, wrong_length: int, check: dict):
    """(checks, correct): each number the configuration gives a limit
    for, beside it; finished requests of the wrong length; and the
    sample's size."""
    checks = {name: {"value": readings[name], "limit": limit}
              for name, limit in check["limits"].items()}
    checks["wrong_length"] = {"value": wrong_length, "limit": 0}
    checks["sampled_tokens"] = {"value": readings["tokens"],
                                "limit": check["min_tokens"]}
    correct = (all(c["value"] <= c["limit"] for n, c in checks.items()
                   if n != "sampled_tokens")
               and readings["tokens"] >= check["min_tokens"])
    return checks, correct


# -- the run ---------------------------------------------------------------------

def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             bench: Path = BENCH, require_chip: bool = True) -> dict:
    import jax
    chips = int(cell.get("chips", 1))
    if require_chip:
        devs, peaks = check_device(chips)
    else:
        devs, peaks = jax.devices(), peaks_for("TPU v5 lite")
    dev = devs[0]
    conf = bmodel.load_config(cell["config"], bench)
    traffic = btraffic.load_traffic(cell["traffic"], bench)
    kinds = archs.of(conf).layer_kinds(conf)
    log(f"device {dev.platform} {dev.device_kind} x{len(devs)}; cell "
        f"{cell['name']}: {conf['name']} ({archs.name_of(conf)}: "
        f"{dict(Counter(kinds))}), {btraffic.summary(traffic)}")
    compiles = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    try:
        return _run(cell, conf, traffic, seed, seconds, trace, bench, dev,
                    devs, peaks, compiles)
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)


def measure(conf: dict, traffic: dict, seed: int, seconds: float,
            trace: bool, devs, compiles: CompileCounter) -> SimpleNamespace:
    """Weights, runtime, warm-up, pre-roll and window on the cell's
    devices ``devs``: everything up to the window's close. The runtime
    and its weights are dropped before returning; ``peak`` is the
    fullest device's."""
    cfg = bmodel.arch_config(conf)
    t = time.perf_counter()
    params = bmodel.init_weights(conf, seed)
    log(f"weights from seed: {time.perf_counter() - t:.1f} s")
    t = time.perf_counter()
    runtime = build_runtime(params, cfg, conf, seed, devs,
                            btraffic.tenants(traffic))
    sink = EventSink()
    for tracer in runtime.tracers:
        tracer.add_sink(sink)
    warm_up(runtime, traffic, conf["vocab_size"], seed)
    log(f"runtime built and warmed up: {time.perf_counter() - t:.1f} s, "
        f"{len(compiles.times)} compiles or cache loads so far")

    arrivals = btraffic.schedule(traffic, seed, seconds, conf["vocab_size"])
    # set-up's objects stay out of the window's collections, as a serving
    # process freezes them after loading
    gc.collect()
    gc.freeze()
    pauses = GcPauses()
    gc.callbacks.append(pauses)
    t0 = time.perf_counter()
    w0 = t0 + traffic["preroll_s"]
    w1 = w0 + seconds
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        recs, steps, trace_iv, queued = serve(runtime, arrivals, t0, w0, w1,
                                              trace_dir)
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devs)
        reduced = None
        if trace_dir is not None:
            files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
            if files:
                reduced = trace_reduce.reduce(trace_reduce.load(files[-1]))
    finally:
        gc.callbacks.remove(pauses)
        gc.unfreeze()
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    by_uid = {r.req.uid: r for r in recs}
    for start, _, _, uid in sink.prefill:
        if uid in by_uid:
            by_uid[uid].prefill_start = start
    del runtime, params
    gc.collect()
    return SimpleNamespace(
        recs=recs, steps=steps, trace_iv=trace_iv, reduced=reduced,
        sink=sink, w0=w0, w1=w1, queued=queued, gc_pauses=pauses,
        peak=peak)


def _run(cell, conf, traffic, seed, seconds, trace, bench, dev, devs, peaks,
         compiles) -> dict:
    m = measure(conf, traffic, seed, seconds, trace,
                cell_devices(cell, devs), compiles)
    setup_s = m.w0 - T_START
    recs, w0, w1, peak, reduced = m.recs, m.w0, m.w1, m.peak, m.reduced
    sink, steps, trace_iv = m.sink, m.steps, m.trace_iv
    in_window = compiles.between(w0, w1)
    e2e = end_to_end(recs, w0, w1)
    lateness = [r.submit_t - r.due for r in recs]
    finished = [r for r in recs if r.req.done]
    wrong_len = [r for r in finished if len(r.req.out) != r.req.max_new]
    log(f"window {seconds:.0f} s: {e2e['n_due']} requests due, "
        f"{sum(w0 <= r.due < w1 and r.req.done for r in recs)} of them "
        f"completed; queued at open {m.queued[0]}, at close {m.queued[1]}; "
        f"{len(finished)} completed in the run, {e2e['n_tokens']} tokens, "
        f"{e2e['n_itl']} gaps")
    log(f"compiles or cache loads inside the window: {in_window}")
    log(f"generator lateness: median {np.median(lateness) * 1e3:.3f} ms, "
        f"max {max(lateness) * 1e3:.3f} ms")
    log(f"peak_bytes_in_use: {peak} ({peak / 1e9:.2f} GB)")
    log_host_and_kv(conf, steps, m.gc_pauses, w0, w1)

    ctx = SimpleNamespace(
        conf=conf, peaks=peaks, slots=conf["serving"]["batch_slots"],
        w0=w0, w1=w1, recs=[r for r in recs if w0 <= r.due < w1],
        prefill=[p for p in sink.prefill if w0 <= p[0] and p[1] <= w1],
        decode=[d for d in sink.decode if w0 <= d[0] and d[1] <= w1],
        steps=steps, trace=reduced, trace_iv=trace_iv,
        memory_peak_bytes=peak)

    sample = pick_sample(finished, seed)
    ref = load_reference(conf, bench)
    t = time.perf_counter()
    params = bmodel.base_weights(conf, seed)
    gaps = served_gaps(ref.make_forward(conf), params, traffic, sample)
    readings = gap_readings(gaps)
    log(f"reference over {len(sample)} requests, {readings['tokens']} "
        f"compared tokens (longest served "
        f"{max((len(r.req.out) for r in sample), default=0)}): "
        f"{time.perf_counter() - t:.1f} s; widest gap "
        f"{readings['logit_gap']}, mean gap {readings['mean_gap']}, "
        f"mismatch share {readings['mismatch_share']}")
    checks, correct = compare(readings, len(wrong_len), conf["check"])

    if trace:
        metrics, breakdown = {}, None
        for m in metrics_of(cell, "per_layer"):
            v = load_reader(m["name"], bench)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device = {"busy_s": reduced.busy_s if reduced else 0.0,
                  "window_s": reduced.window_s if reduced else 0.0}
        if reduced is not None:
            breakdown = {"device_ops": [list(x) for x in reduced.top_ops],
                         "idle_gaps": [list(x) for x in reduced.idle_gaps]}
            log(f"trace: busy {reduced.busy_s:.4f} s of "
                f"{reduced.window_s:.4f} s; host spans {reduced.spans}")
    else:
        values = dict(e2e, setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in metrics_of(cell, "end_to_end")
                   if values.get(m["name"]) is not None}
        device, breakdown = {}, None
    result = {"correct": bool(correct), "attempted": e2e["n_due"],
              "failed": len(wrong_len), "metrics": metrics,
              "device": dict(platform=dev.platform, kind=dev.device_kind,
                             count=len(devs), memory_peak_bytes=peak,
                             **device)}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']} limit {c['limit']}")
    return result


def setup_jax() -> str:
    """The program's persistent compile cache, keeping every program."""
    from repro.launch.compile_cache import setup_compile_cache
    cache = setup_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        import repro  # noqa: F401
    except ImportError as e:
        log(f"the program is not in this checkout: {e}")
        return 2
    try:
        cell = load_cell(args.workload)
        log(f"compile cache {setup_jax()}")
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except Failure as e:
        log(f"FAILED: {e}")
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
