"""Concurrent-execution layer — the ACE analogue on TPU (paper §6).

MI300A exposes hardware ACE queues that time/space-share one GPU. A TPU
chip runs one program at a time, so the framework provides the two
TPU-idiomatic concurrency mechanisms and instruments both with the paper's
metrics (overlap efficiency, fairness, per-stream CV):

* ``run_async_dispatch``  — one device (set), N workloads enqueued through
  JAX's runahead queue: time-multiplexing, the moral equivalent of N HSA
  queues feeding one ACE. Aggregate throughput rises; per-stream latency
  becomes contention-dependent — the paper's fairness collapse reproduces
  here.
* ``run_spatial``         — N disjoint device subsets, one workload each:
  space-multiplexing (sub-mesh multi-tenancy). TPU can give what MI300A
  cannot: *hard isolation* (no shared L2/LDS), at the cost of peak
  per-stream throughput.

``OccupancyAdvisor`` encodes the paper's §9.2 guidance as executable
policy (used by the serving layer and the examples).
"""
from __future__ import annotations

import dataclasses
import os
import time
import warnings
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import numpy as np

# CPU containers report no grid-parallelism capacity; the advisor's fill
# priors were set against this TPU-class table value (paper Table 1
# adaptation), so CPU runs keep it and test behaviour stays stable.
DEFAULT_N_CORES = 256

# MXUs per chip by ``device_kind`` — each MXU consumes one 128x128 output
# tile at a time, the unit ``execution.grid_tiles`` counts. Google Cloud
# TPU system-architecture pages: v4 and v5p have 2 TensorCores x 4 MXUs,
# v5e has 1 TensorCore x 4 MXUs.
TPU_MXUS = {
    "TPU v4": 8,
    "TPU v5": 8,
    "TPU v5p": 8,
    "TPU v5 lite": 4,
    "TPU v5e": 4,
}


def detect_core_count(default: int = DEFAULT_N_CORES) -> int:
    """Grid-parallelism capacity of the attached accelerator(s).

    Precedence: ``REPRO_N_CORES`` env override > ``default`` on a CPU
    backend > the summed per-chip MXU count (:data:`TPU_MXUS`, or a GPU's
    ``core_count``). An accelerator whose capacity is unknown raises: a
    guessed count would skew every fill-denominated threshold.
    """
    env = os.environ.get("REPRO_N_CORES")
    if env:
        try:
            val = int(env)
        except ValueError:
            warnings.warn(
                f"REPRO_N_CORES={env!r} is not an integer; ignoring the "
                f"override and falling back to detection/default",
                RuntimeWarning, stacklevel=2)
        else:
            if val > 0:
                return val
            warnings.warn(
                f"REPRO_N_CORES={env!r} is not a positive core count; "
                f"ignoring the override and falling back to "
                f"detection/default",
                RuntimeWarning, stacklevel=2)
    devices = jax.devices()
    if devices[0].platform == "cpu":
        return default
    total = 0
    for d in devices:
        per = TPU_MXUS.get(d.device_kind) if d.platform == "tpu" \
            else getattr(d, "core_count", None)
        if not per:
            raise ValueError(
                f"no core count known for {d.platform} device kind "
                f"{d.device_kind!r}; add it to TPU_MXUS or set REPRO_N_CORES")
        total += int(per)
    return total


# ---------------------------------------------------------------------------
# Metrics (paper §4.2)
# ---------------------------------------------------------------------------

def fairness_raw(times: Sequence[float]) -> float:
    """Unclamped 1 - (t_max - t_min)/t_mean ∈ (-inf, 1]. Diagnostic only:
    below 0 the spread exceeds the mean and the magnitude is not
    interpretable as a fairness level."""
    t = np.asarray(times, dtype=np.float64)
    if t.size == 0 or t.mean() == 0:
        return 1.0
    return float(1.0 - (t.max() - t.min()) / t.mean())


def fairness(times: Sequence[float]) -> float:
    """1 - (t_max - t_min)/t_mean clamped to [0, 1].

    Paper convention: the fairness index is reported in [0, 1] (Fig 5:
    0.016–0.138 at 8 streams), 1.0 = perfectly balanced, 0.0 = fully
    collapsed. The raw expression goes arbitrarily negative for skewed
    streams (spread > mean), which is meaningless as a *level* — use
    :func:`fairness_raw` when the unbounded value is wanted."""
    return max(0.0, fairness_raw(times))


def fairness_min_max(times: Sequence[float]) -> float:
    """min/max per-stream time ratio (paper §7.2 variant); 1.0 = balanced."""
    t = np.asarray(times, dtype=np.float64)
    if t.size == 0 or t.max() == 0:
        return 1.0
    return float(t.min() / t.max())


def cv(times: Sequence[float]) -> float:
    t = np.asarray(times, dtype=np.float64)
    if t.size == 0 or t.mean() == 0:
        return 0.0
    return float(t.std() / t.mean())


def latency_percentiles(times: Sequence[float],
                        ps: Sequence[int] = (50, 99)) -> Dict[str, float]:
    """{"p50": ..., "p99": ...} over a latency sample (paper Fig 8's
    per-stream distribution view); zeros when the sample is empty."""
    t = np.asarray(times, dtype=np.float64)
    if t.size == 0:
        return {f"p{p}": 0.0 for p in ps}
    return {f"p{p}": float(np.percentile(t, p)) for p in ps}


def overlap_efficiency(serial_total: float, concurrent_total: float,
                       n_streams: int) -> float:
    """Fraction of ideal overlap achieved: 1.0 when concurrent time equals
    serial/n (perfect overlap), 0.0 when no faster than serial."""
    if serial_total <= 0 or n_streams <= 1:
        return 0.0
    ideal = serial_total / n_streams
    if concurrent_total <= ideal:
        return 1.0
    return float((serial_total - concurrent_total)
                 / (serial_total - ideal))


@dataclasses.dataclass
class StreamReport:
    n_streams: int
    mode: str                        # serial | async | spatial
    per_stream_s: List[float]
    wall_s: float
    serial_wall_s: float
    speedup: float
    overlap_efficiency: float
    fairness: float
    fairness_min_max: float
    cv: float
    # How per_stream_s was measured. "dispatch_to_ready" (the lane-handle
    # clock: each stream's time runs from ITS OWN dispatch to its result
    # being ready) is the only mode produced since the lane refactor.
    timing: str = "dispatch_to_ready"

    def to_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        for k, v in d.items():
            if isinstance(v, float):
                d[k] = round(v, 9)
            elif isinstance(v, list):
                d[k] = [round(x, 9) if isinstance(x, float) else x
                        for x in v]
        # keep numbers comparable across the timing change: pre-lane
        # reports measured every stream from one global t0 (so a late
        # stream's time included every earlier stream's completion wait)
        d["legacy_timing"] = ("pre-lane per_stream_s ran from a global t0"
                              " — not per-dispatch")
        return d

    def to_record(self, name: str, **extra: Any):
        """Serialize as a :class:`repro.core.characterization.Record` —
        the one schema fig4/fig5 CSVs, ``dump_records``/``load_records``
        and ``AutotuneStore.add_records`` all consume. ``extra`` keys are
        merged into ``derived`` (e.g. ``precision=...``, ``streams=...``)."""
        from repro.core.characterization import Record
        derived = dict(self.to_dict())
        derived.update(extra)
        return Record(name=name, us_per_call=self.wall_s * 1e6,
                      derived=derived)


# ---------------------------------------------------------------------------
# Execution lanes (dispatch-and-join seam)
# ---------------------------------------------------------------------------

def _block(x):
    jax.tree.map(lambda a: a.block_until_ready()
                 if hasattr(a, "block_until_ready") else a, x)


@dataclasses.dataclass
class LaneHandle:
    """A joinable in-flight dispatch.

    ``result`` holds whatever the thunk returned — with JAX async dispatch
    that's future-backed arrays already enqueued on the device, not yet
    blocked on. ``join()`` blocks until ready and stamps ``ready_t``;
    ``dispatch_to_ready_s`` is then the stream's own dispatch→ready time
    (NOT measured from some global start, so it excludes other streams'
    completion waits when dispatch outpaces execution)."""
    lane: str
    label: str
    result: Any
    dispatch_t: float
    overlap_group: int = -1
    ready_t: Optional[float] = None

    def join(self) -> Any:
        if self.ready_t is None:
            _block(self.result)
            self.ready_t = time.perf_counter()
        return self.result

    @property
    def done(self) -> bool:
        return self.ready_t is not None

    @property
    def dispatch_to_ready_s(self) -> float:
        end = self.ready_t if self.ready_t is not None else time.perf_counter()
        return max(0.0, end - self.dispatch_t)


class ExecutionLane:
    """A named async dispatch context — the ACE-queue analogue the rest of
    the stack programs against.

    ``dispatch(thunk)`` calls the thunk immediately (with JAX that enqueues
    the computation through the runahead queue and returns future arrays)
    and wraps the un-blocked result in a :class:`LaneHandle`. Callers join
    handles when — and only when — they need the values on the host, which
    is what lets two lanes' work genuinely overlap. A lane given a
    ``tracer`` (duck-typed ``repro.runtime.telemetry.Tracer``) records one
    ``dispatch`` event per dispatch so overlap decisions are attributable
    after the fact.

    ``handles`` holds the lane's un-joined dispatches; a handle joined
    elsewhere is dropped at the next dispatch, so a long-lived lane does
    not keep every past result (a serving step's whole KV cache) alive."""

    def __init__(self, name: str = "lane0", *, index: int = 0, tracer=None):
        self.name = name
        self.index = index
        self.tracer = tracer
        self.handles: List[LaneHandle] = []

    def dispatch(self, thunk: Callable[[], Any], *, label: str = "",
                 overlap_group: int = -1) -> LaneHandle:
        t0 = time.perf_counter()
        result = thunk()               # enqueued via JAX async dispatch
        h = LaneHandle(lane=self.name,
                       label=label or getattr(thunk, "__name__", "thunk"),
                       result=result, dispatch_t=t0,
                       overlap_group=overlap_group)
        self.handles = [old for old in self.handles if not old.done]
        self.handles.append(h)
        if self.tracer is not None:
            self.tracer.record("dispatch", lane=self.name,
                               overlap_group=overlap_group,
                               meta={"label": h.label})
        return h

    def join_all(self) -> List[Any]:
        out = [h.join() for h in self.handles]
        self.handles.clear()
        return out

    def reset(self) -> None:
        self.handles.clear()

    def __repr__(self) -> str:  # pragma: no cover - debugging nicety
        return (f"ExecutionLane({self.name!r}, index={self.index}, "
                f"inflight={sum(not h.done for h in self.handles)})")


# ---------------------------------------------------------------------------
# Stream runners (rebuilt on lanes)
# ---------------------------------------------------------------------------

def run_serial(thunks: Sequence[Callable[[], Any]],
               lane: Optional[ExecutionLane] = None) -> List[float]:
    """Execute each workload to completion before the next; returns
    per-stream durations."""
    lane = lane if lane is not None else ExecutionLane("serial")
    times = []
    for fn in thunks:
        h = lane.dispatch(fn)
        h.join()
        times.append(h.dispatch_to_ready_s)
    return times


def run_async_dispatch(thunks: Sequence[Callable[[], Any]],
                       lane: Optional[ExecutionLane] = None) -> List[float]:
    """Enqueue all workloads through the JAX dispatch queue, then join in
    dispatch order — the ACE multi-queue analogue. Returns each stream's
    own dispatch→ready time (see :class:`LaneHandle`): a late stream is no
    longer charged for earlier streams' completion waits, which the old
    global-t0 measurement did whenever dispatch outpaced execution."""
    lane = lane if lane is not None else ExecutionLane("async")
    handles = [lane.dispatch(fn) for fn in thunks]   # all enqueued
    times = []
    for h in handles:
        h.join()
        times.append(h.dispatch_to_ready_s)
    return times


def run_spatial(fns_and_args: Sequence[tuple], devices: Sequence) -> List[float]:
    """One workload per device (subset): spatial multi-tenancy.

    ``fns_and_args[i] = (jitted_fn_on_device_i, args)``; returns per-stream
    completion times from the common start."""
    t0 = time.perf_counter()
    results = [fn(*args) for fn, args in fns_and_args]
    times = []
    for r in results:
        _block(r)
        times.append(time.perf_counter() - t0)
    return times


def characterize_streams(make_thunk: Callable[[int], Callable[[], Any]],
                         n_streams: int, *, warmup: int = 1,
                         mode: str = "async", tracer=None) -> StreamReport:
    """Run the paper's Fig-4/5 experiment for one stream count.

    ``tracer`` (a :class:`repro.runtime.telemetry.Tracer`, duck-typed)
    receives one ``stream`` event per stream with its measured completion
    time plus a ``stream_report`` aggregate — the §6 observables feeding
    the online calibration loop."""
    thunks = [make_thunk(i) for i in range(n_streams)]
    # warm EVERY thunk: each stream may be a distinct jitted computation
    # (or a distinct shape), and any compilation left for the timed region
    # lands on the early streams and inflates their times.
    for _ in range(warmup):
        for fn in thunks:
            _block(fn())

    serial_times = run_serial(thunks)
    serial_total = sum(serial_times)

    t0 = time.perf_counter()
    if mode == "async":
        per_stream = run_async_dispatch(thunks)
    else:
        per_stream = run_serial(thunks)
    wall = time.perf_counter() - t0

    report = StreamReport(
        n_streams=n_streams,
        mode=mode,
        per_stream_s=per_stream,
        wall_s=wall,
        serial_wall_s=serial_total,
        speedup=serial_total / wall if wall > 0 else 0.0,
        overlap_efficiency=overlap_efficiency(serial_total, wall, n_streams),
        fairness=fairness(per_stream),
        fairness_min_max=fairness_min_max(per_stream),
        cv=cv(per_stream),
    )
    if tracer is not None:
        for i, s in enumerate(per_stream):
            tracer.record_stream(i, s, mode=mode, n_streams=n_streams)
        tracer.record("stream_report", wall_s=wall, meta={
            "mode": mode, "n_streams": n_streams,
            "fairness": report.fairness, "cv": report.cv,
            "overlap_efficiency": report.overlap_efficiency})
    return report


# ---------------------------------------------------------------------------
# Occupancy advisor (paper §9.2 as executable policy)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class WorkloadProfile:
    precision: str                  # fp8 | fp16 | bf16 | fp32
    grid_tiles: int                 # parallelism available (TPU: MXU tiles)
    latency_sensitive: bool = False
    concurrent_tenants: int = 1


@dataclasses.dataclass
class Advice:
    use_sparsity: bool
    max_streams: int
    suggested_precision: str
    batch_multiplier: int
    rationale: List[str]


class OccupancyAdvisor:
    """Paper §9.2 decision rules, re-based on the TPU adaptation:

    * FP8 needs ~2× the grid parallelism of bf16 to hide HBM latency
      (paper: 256+ wavefronts vs 192/128) — below the threshold, prefer
      bf16 or batch up.
    * concurrency: ≤4 streams for latency-sensitive (fairness > 0.5),
      6–8 for throughput; hard isolation → spatial sub-meshes.
    * sparsity: enable when the workload is memory-bound/multi-tenant
      (TPU: decode, small batch); disable for isolated compute-bound work.
    """

    # TPU v5e-class threshold: ~1 MXU tile per core with double-buffering.
    # These class constants are the *priors* (Table-3/§9.2 values); an
    # instance built by core/autotune carries measured ones instead.
    FP8_TILE_THRESHOLD = 2.0        # ×cores
    BF16_TILE_THRESHOLD = 1.0

    def __init__(self, n_cores: Optional[int] = None, *,
                 fp8_fill_target: Optional[float] = None,
                 demote_below_fill: Optional[float] = None,
                 calibrated: bool = False):
        self.n_cores = n_cores if n_cores is not None else detect_core_count()
        self.fp8_fill_target = self.FP8_TILE_THRESHOLD \
            if fp8_fill_target is None else float(fp8_fill_target)
        self.demote_below_fill = self.BF16_TILE_THRESHOLD \
            if demote_below_fill is None else float(demote_below_fill)
        self.calibrated = calibrated

    def advise(self, w: WorkloadProfile) -> Advice:
        rationale = []
        precision = w.precision
        batch_mult = 1
        src = "measured" if self.calibrated else "paper §9.2"
        fill = w.grid_tiles / self.n_cores
        if w.precision in ("fp8",) and fill < self.fp8_fill_target:
            if fill < self.demote_below_fill:
                precision = "bf16"
                rationale.append(
                    f"grid fill {fill:.2f}× cores < "
                    f"{self.demote_below_fill:g}"
                    f"× ({src}) needed for FP8 to hide HBM latency; bf16 "
                    "is faster at this occupancy ('FP16 at 128 wavefronts "
                    "outperforms underutilized FP8')")
            else:
                batch_mult = int(np.ceil(self.fp8_fill_target / fill))
                rationale.append(
                    f"batch ×{batch_mult} to reach FP8 occupancy threshold "
                    f"({src})")
        max_streams = 4 if w.latency_sensitive else 8
        if w.latency_sensitive and w.concurrent_tenants > 4:
            rationale.append(
                "latency-sensitive with >4 tenants: prefer spatial sub-mesh "
                "isolation over queue concurrency (fairness collapses at 8 "
                "streams: 0.016–0.138 in the paper)")
        use_sparsity = w.concurrent_tenants > 1 or w.latency_sensitive is False
        if w.concurrent_tenants == 1 and w.grid_tiles >= self.n_cores:
            use_sparsity = False
            rationale.append(
                "isolated compute-bound workload: 2:4 sparsity is break-even "
                "(paper §7.1) — disabled")
        else:
            rationale.append(
                "memory-bound/multi-tenant context: 2:4 packed weights cut "
                "HBM weight traffic (TPU adaptation of paper §7.2's "
                "concurrency-dependent win)")
        return Advice(use_sparsity=use_sparsity, max_streams=max_streams,
                      suggested_precision=precision,
                      batch_multiplier=batch_mult, rationale=rationale)
