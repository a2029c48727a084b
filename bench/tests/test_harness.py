"""The harness end to end on the CPU at a tiny size, past its chip check.

The correctness check must hold on a sound run and fail when a served
token is altered where it is produced. Without a TPU, and without the
program beside it, a run exits non-zero and prints no result.
"""
import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import numpy as np

from bench import control
from bench import run as R

from .conftest import BENCH, ROOT


def _run(tiny_bench, trace=False, seed=2**31 + 11):
    root, cell = tiny_bench
    return R.run_cell(cell, seed, 2.0, trace, bench=root,
                      require_chip=False)


def test_sound_run_is_correct(tiny_bench):
    res = _run(tiny_bench)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"ttft_p90_ms", "itl_p95_ms",
                                   "output_tokens_per_s", "setup_s"}
    assert list(res)[-1] == "checks"
    assert res["checks"]["sampled_tokens"]["value"] >= 20


def test_sample_covers_several_requests_live_together():
    def rec(uid, n, t0, t1):
        r = R.Rec(due=t0, req=SimpleNamespace(
            uid=uid, out=[0] * n, prompt=[0] * 8))
        r.stamps = list(np.linspace(t0, t1, n))
        return r
    longest = rec(0, 512, 10.0, 50.0)
    beside = [rec(i, 100, 20.0 + i, 30.0 + i) for i in range(1, 9)]
    apart = [rec(i, 100, 60.0, 70.0) for i in range(9, 20)]
    sample = R.pick_sample(apart + beside + [longest], seed=3)
    assert sample[0] is longest
    assert R.SAMPLE_MIN <= len(sample) <= R.SAMPLE_MAX
    assert all(r in beside for r in sample[1:])
    pos = R.compared_positions(512)
    assert len(pos) == R.PER_REQUEST and pos[0] == 0 and pos[-1] == 511
    assert list(R.compared_positions(5)) == [0, 1, 2, 3, 4]


def test_traced_run_gives_per_layer_metrics(tiny_bench):
    res = _run(tiny_bench, trace=True)
    assert res["correct"]
    # a CPU trace has no device plane: device readers stay silent
    assert {"queue_wait_p90_ms", "batch_occupancy", "prefill_ms_per_ktok",
            "decode_step_ms"} <= set(res["metrics"])
    assert "device_idle_share" not in res["metrics"]
    # a metric split by the cells it moves reads as the one it splits
    assert res["metrics"]["decode_step_ms.overload"] == \
        res["metrics"]["decode_step_ms"]
    assert "window_s" in res["device"] and "busy_s" in res["device"]


def test_altered_token_is_caught(tiny_bench, monkeypatch):
    from repro.runtime.serve_loop import ServeSession
    join = ServeSession.join_decode
    vocab = 300

    def altered(self, ticket):
        done = join(self, ticket)
        for req in [r for r in self.slots if r is not None] + done:
            if len(req.out) >= 2:
                req.out[-1] = (req.out[-1] + 1) % vocab
        return done

    monkeypatch.setattr(ServeSession, "join_decode", altered)
    res = _run(tiny_bench)
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > \
        res["checks"]["logit_gap"]["limit"]
    assert res["checks"]["mean_gap"]["value"] > \
        res["checks"]["mean_gap"]["limit"]


def test_control_fails_the_limit(tiny_bench):
    """The reference in float8 in the program's place reads past a limit
    on every seed, where the program stays within all of them."""
    root, cell = tiny_bench
    limits = json.loads((root / "configs" / "tiny.json").read_text())[
        "check"]["limits"]
    rows = control.readings(cell, [3, 2**31 + 5, 77], 2.0,
                            require_chip=False, bench=root)
    for r in rows:
        assert any(r["control"][n] > lim for n, lim in limits.items()), r
        assert all(r["program"][n] <= lim for n, lim in limits.items()), r
        assert r["control_correct"] is False and r["program_correct"], r


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "granite-moe.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    out = _cli(ROOT)
    assert out.returncode != 0 and out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _cli(tmp_path, {"PYTHONPATH": ""})
    assert out.returncode != 0 and out.stdout.strip() == ""
