"""Concurrency layer: metric properties + stream characterization runs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import concurrency as cc


def test_fairness_bounds():
    assert cc.fairness([1.0, 1.0, 1.0]) == 1.0
    assert cc.fairness([1.0, 2.0]) == pytest.approx(1 - 1 / 1.5)
    assert cc.fairness([]) == 1.0
    # paper convention: fairness is reported in [0, 1] — severe imbalance
    # clamps to 0.0 (full collapse); the unbounded diagnostic is
    # fairness_raw (paper reports 0.016 at 8 streams, still in range)
    assert cc.fairness([0.1, 10.0]) == 0.0
    assert cc.fairness_raw([0.1, 10.0]) < 0.0
    assert 0.0 <= cc.fairness([0.1, 10.0, 0.5]) <= 1.0


def test_latency_percentiles():
    p = cc.latency_percentiles([1.0, 2.0, 3.0, 4.0])
    assert p["p50"] == pytest.approx(2.5)
    assert p["p99"] <= 4.0
    assert cc.latency_percentiles([]) == {"p50": 0.0, "p99": 0.0}


def test_characterize_streams_warms_every_thunk():
    calls = []

    def mk(i):
        def thunk():
            calls.append(i)
            return jnp.zeros(())
        return thunk

    cc.characterize_streams(mk, 3, warmup=1, mode="async")
    # warmup (one pass over ALL streams) + serial pass + async pass
    assert calls[:3] == [0, 1, 2]
    assert len(calls) == 9


def test_fairness_min_max():
    assert cc.fairness_min_max([2.0, 2.0]) == 1.0
    assert cc.fairness_min_max([1.0, 4.0]) == 0.25


def test_cv():
    assert cc.cv([1.0, 1.0]) == 0.0
    assert cc.cv([1.0, 3.0]) == pytest.approx(0.5)


def test_overlap_efficiency():
    # perfect overlap: 4 streams of 1s each complete in 1s total
    assert cc.overlap_efficiency(4.0, 1.0, 4) == 1.0
    # no overlap: concurrent == serial
    assert cc.overlap_efficiency(4.0, 4.0, 4) == 0.0
    # halfway
    assert cc.overlap_efficiency(4.0, 2.5, 4) == pytest.approx(0.5)


def test_characterize_streams_runs():
    def mk(i):
        x = jax.random.normal(jax.random.PRNGKey(i), (128, 128))
        f = jax.jit(lambda a: (a @ a).sum())
        return lambda: f(x)
    rep = cc.characterize_streams(mk, 2, mode="async")
    assert rep.n_streams == 2
    assert len(rep.per_stream_s) == 2
    assert rep.wall_s > 0 and rep.serial_wall_s > 0
    assert -5.0 <= rep.fairness <= 1.0
    d = rep.to_dict()
    assert set(d) >= {"speedup", "overlap_efficiency", "fairness", "cv"}


def test_run_serial_returns_per_stream():
    f = jax.jit(lambda a: a * 2)
    x = jnp.ones((8, 8))
    times = cc.run_serial([lambda: f(x)] * 3)
    assert len(times) == 3 and all(t > 0 for t in times)


# ---------------------------------------------------------------------------
# Occupancy advisor (paper §9.2 rules)
# ---------------------------------------------------------------------------

def test_advisor_fp8_low_occupancy_prefers_bf16():
    adv = cc.OccupancyAdvisor(n_cores=256)
    a = adv.advise(cc.WorkloadProfile(precision="fp8", grid_tiles=128,
                                      latency_sensitive=True))
    assert a.suggested_precision == "bf16"
    assert any("HBM latency" in r for r in a.rationale)


def test_advisor_fp8_mid_occupancy_batches_up():
    adv = cc.OccupancyAdvisor(n_cores=256)
    a = adv.advise(cc.WorkloadProfile(precision="fp8", grid_tiles=300))
    assert a.suggested_precision == "fp8"
    assert a.batch_multiplier >= 2


def test_advisor_sparsity_context_dependent():
    adv = cc.OccupancyAdvisor(n_cores=256)
    # isolated compute-bound: break-even -> off (paper §7.1)
    iso = adv.advise(cc.WorkloadProfile(precision="bf16", grid_tiles=1024,
                                        latency_sensitive=True,
                                        concurrent_tenants=1))
    assert not iso.use_sparsity
    # multi-tenant: on (paper §7.2)
    multi = adv.advise(cc.WorkloadProfile(precision="bf16", grid_tiles=1024,
                                          latency_sensitive=True,
                                          concurrent_tenants=4))
    assert multi.use_sparsity


def test_advisor_stream_limits():
    adv = cc.OccupancyAdvisor()
    lat = adv.advise(cc.WorkloadProfile("bf16", 512, latency_sensitive=True))
    thr = adv.advise(cc.WorkloadProfile("bf16", 512, latency_sensitive=False))
    assert lat.max_streams == 4 and thr.max_streams == 8


# ---------------------------------------------------------------------------
# Execution lanes (dispatch-and-join seam)
# ---------------------------------------------------------------------------

def test_lane_handle_join_and_timing():
    lane = cc.ExecutionLane("l0")
    f = jax.jit(lambda a: (a @ a).sum())
    x = jnp.ones((64, 64))
    h = lane.dispatch(lambda: f(x), label="gemm", overlap_group=3)
    assert h.lane == "l0" and h.label == "gemm" and h.overlap_group == 3
    out = h.join()
    assert float(out) == pytest.approx(64.0 * 64 * 64)
    assert h.done and h.dispatch_to_ready_s > 0
    ready = h.ready_t
    assert h.join() is out             # idempotent: ready_t stamped once
    assert h.ready_t == ready
    assert lane.join_all() == [out]


def test_lane_keeps_only_unjoined_handles():
    """A long-lived lane (one per serving partition) must not pin every
    past result: joined handles drop out at the next dispatch."""
    lane = cc.ExecutionLane("l0")
    for i in range(5):
        lane.dispatch(lambda i=i: jnp.full((4,), i), label="step").join()
    assert len(lane.handles) == 1
    pending = lane.dispatch(lambda: jnp.ones(()), label="open")
    assert lane.handles == [pending]
    assert [float(v) for v in lane.join_all()] == [1.0]
    assert lane.handles == []


def test_lane_dispatch_returns_before_join():
    """Dispatch enqueues; the handle is not ready until joined."""
    lane = cc.ExecutionLane("l0")
    h = lane.dispatch(lambda: jnp.zeros(()), label="z")
    assert not h.done and h.ready_t is None
    h.join()
    assert h.done


def test_run_async_dispatch_per_handle_timing():
    """Satellite regression: per-stream times are per-handle
    dispatch->ready, not offsets from one global t0 — so they no longer
    sum to more than the wall just because a stream joined late."""
    f = jax.jit(lambda a: (a @ a).sum())
    xs = [jax.random.normal(jax.random.PRNGKey(i), (128, 128))
          for i in range(3)]
    times = cc.run_async_dispatch([lambda x=x: f(x) for x in xs])
    assert len(times) == 3 and all(t > 0 for t in times)


def test_stream_report_legacy_timing_note():
    def mk(i):
        return lambda: jnp.zeros(())
    rep = cc.characterize_streams(mk, 2, mode="async")
    assert rep.timing == "dispatch_to_ready"
    d = rep.to_dict()
    assert "per_stream_s" in d and "legacy_timing" in d
    assert "global t0" in d["legacy_timing"]


def test_stream_report_to_record_round_trips():
    """fig4/fig5 share one Record schema with the autotune store."""
    from repro.core import autotune
    def mk(i):
        return lambda: jnp.zeros(())
    rep = cc.characterize_streams(mk, 2, mode="async")
    rec = rep.to_record("fig4/test/streams=2", streams=2)
    assert rec.us_per_call == pytest.approx(rep.wall_s * 1e6)
    assert rec.derived["streams"] == 2
    d = autotune.record_to_dict(rec)
    per_stream = d["derived"]["per_stream_s"]
    assert isinstance(per_stream, list) and len(per_stream) == 2
    store = autotune.AutotuneStore()
    store.add_records([rec])           # stream records ingest cleanly


# ---------------------------------------------------------------------------
# REPRO_N_CORES env validation
# ---------------------------------------------------------------------------

def test_detect_core_count_env_valid(monkeypatch):
    monkeypatch.setenv("REPRO_N_CORES", "37")
    assert cc.detect_core_count() == 37


@pytest.mark.parametrize("bad", ["notanum", "0", "-3", "1.5"])
def test_detect_core_count_env_invalid_warns_and_falls_back(
        monkeypatch, bad):
    monkeypatch.setenv("REPRO_N_CORES", bad)
    with pytest.warns(RuntimeWarning, match="REPRO_N_CORES"):
        assert cc.detect_core_count(default=99) == 99
