"""The trace reduction on a small trace recorded on one TPU v5e.

``data/small_tpu.xplane.pb`` was written by ``record_trace.py`` on the
chip: three rounds of a 2048x2048 bf16 matmul program and an elementwise
program inside ``bench.step`` spans, each round followed by a 20 ms
``bench.idle_wait``, all inside one ``bench.trace_window``.
"""
from bench import trace_reduce as T

from .conftest import DATA


def _reduced():
    return T.reduce(T.load(DATA / "small_tpu.xplane.pb"))


def test_window_busy_and_idle():
    r = _reduced()
    assert r.n_devices == 1                      # the TPU plane only
    assert 0.06 < r.window_s < 0.2               # three 20 ms sleeps
    assert 0 < r.busy_s < 0.1 * r.window_s
    assert abs(r.spans["idle_wait"] - 0.06) < 0.01
    # the long gaps are the sleeps, attributed to what the host was doing
    assert [name for name, _ in r.idle_gaps[:3]] == ["idle_wait"] * 3
    assert all(0.015 < s < 0.03 for _, s in r.idle_gaps[:3])
    total_idle = r.window_s - r.busy_s
    assert sum(s for _, s in r.idle_gaps) <= total_idle + 1e-9


def test_programs_and_top_operations():
    r = _reduced()
    runs = T.program_runs(r, "jit__lambda")
    assert 4 <= len(runs) <= 6
    assert all(0 <= start <= r.window_s and 0 < s < 1e-3
               for start, s in runs)
    names = [n for n, _ in r.top_ops]
    assert names and all(n.startswith("jit__lambda/") for n in names)
    assert sum(s for _, s in r.top_ops) >= r.busy_s * 0.99


def test_names():
    assert T.op_name("%fusion.12 = bf16[2]{0} fusion(x)") == "fusion.12"
    assert T.program_name("jit_prefill_step(1234)") == "jit_prefill_step"
    assert T._merge([(5, 7), (0, 2), (1, 3), (6, 9)]) == [(0, 3), (5, 9)]
