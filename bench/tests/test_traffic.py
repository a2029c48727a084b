"""The wall-clock traffic generator and finding files by name."""
import collections
import hashlib
import json
import struct

import numpy as np
import pytest

from bench import model, traffic
from bench import run as R

MIX = {"arrivals": "stratified_exponential", "rate_per_s": 2.3,
       "preroll_s": 12.0,
       "prompt": {"lengths": [128, 256, 512, 1024],
                  "weights": [0.2, 0.3, 0.3, 0.2]},
       "output": {"dist": "lognormal", "median": 128, "sigma": 0.8,
                  "min": 16, "max": 512}}


def _key(sched):
    return [(a.uid, a.due_s, a.prompt.tobytes(), a.max_new) for a in sched]


def test_same_seed_same_schedule():
    seed = 2**31 + 977
    a = traffic.schedule(MIX, seed, 45, 49155)
    b = traffic.schedule(MIX, seed, 45, 49155)
    assert _key(a) == _key(b)
    assert len(a) == round(2.3 * 57)
    assert all(x.due_s <= y.due_s for x, y in zip(a, a[1:]))


def test_seeds_permute_one_multiset_of_work():
    a = traffic.schedule(MIX, 1, 45, 49155)
    b = traffic.schedule(MIX, 2, 45, 49155)
    assert _key(a) != _key(b)
    for f in (lambda s: len(s.prompt), lambda s: s.max_new):
        assert sorted(map(f, a)) == sorted(map(f, b))
    # the gaps are one multiset: the arrivals span the same time but the
    # permuted first gap, which the schedule drops
    gaps = -np.log1p(-(np.arange(len(a)) + 0.5) / len(a)) / MIX["rate_per_s"]
    assert abs(a[-1].due_s - b[-1].due_s) <= gaps.max()


def test_every_block_holds_the_whole_mixture():
    """Dealt by rank, each block of ten has the mix's proportions."""
    mix = dict(MIX, rate_per_s=100 / 57)          # 100 requests
    sched = traffic.schedule(mix, 5, 45, 49155)
    assert len(sched) == 100
    for b in range(10):
        lens = sorted(len(a.prompt) for a in sched[10 * b:10 * b + 10])
        assert lens == [128] * 2 + [256] * 3 + [512] * 3 + [1024] * 2
        outs = sorted(a.max_new for a in sched[10 * b:10 * b + 10])
        assert outs[0] < 128 < outs[-1]


def test_mixture_in_exact_proportions():
    lens = traffic.prompt_lengths(MIX, 100)
    assert [int((lens == l).sum()) for l in (128, 256, 512, 1024)] == \
        [20, 30, 30, 20]
    outs = traffic.output_lengths(MIX, 1000)
    assert outs.min() >= 16 and outs.max() <= 512
    assert abs(np.median(outs) - 128) <= 2
    uni = {"dist": "uniform", "min": 8, "max": 64}
    u = traffic.output_lengths({"output": uni}, 570)
    assert u.min() == 8 and u.max() == 64


def test_new_files_are_found_by_name(tmp_path):
    """A new configuration, traffic mix and metric reader in a fresh
    directory are found by the names a cell gives, with no edit."""
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "metrics").mkdir()
    conf = {"name": "new-model", "hidden_size": 8}
    (tmp_path / "configs" / "new-model.json").write_text(json.dumps(conf))
    (tmp_path / "traffic" / "new-mix.json").write_text(json.dumps(MIX))
    (tmp_path / "metrics" / "new_metric.py").write_text(
        "def read(ctx):\n    return ctx.value * 2\n")
    assert model.load_config("new-model", tmp_path) == conf
    assert traffic.load_traffic("new-mix", tmp_path) == MIX
    read = R.load_reader("new_metric", tmp_path)

    class Ctx:
        value = 21
    assert read(Ctx) == 42
    spec = {"workloads": [{"name": "new-model.new-mix", "config": "new-model",
                           "traffic": "new-mix", "chips": 1}],
            "end_to_end": [{"name": "setup_s", "unit": "s"}],
            "per_layer": [{"name": "new_metric", "unit": "x",
                           "workloads": ["new-model.new-mix"]}]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = R.load_cell("new-model.new-mix", tmp_path)
    assert [m["name"] for m in R.metrics_of(cell, "per_layer")] == \
        ["new_metric"]


def _digest(sched) -> str:
    h = hashlib.sha256()
    for a in sched:
        h.update(struct.pack("<qdq", a.uid, a.due_s, a.max_new))
        h.update(a.prompt.tobytes())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("name,vocab,want", [
    ("granite-moe.chat", 49155, "c0dc76befc78f4cb"),
    ("deepseek-llm-67b.code", 102400, "e58b39a80affbac6"),
    ("granite-moe.chat-overload", 49155, "30a48aa6b535463a"),
])
def test_file_without_tenants_gives_the_parents_schedule(name, vocab, want):
    """The schedule that the generator of commit 3904110, before tenants,
    makes of each file, bit for bit."""
    sched = traffic.schedule(traffic.load_traffic(name), 2**31 + 977, 45,
                             vocab)
    assert _digest(sched) == want
    assert {a.tenant for a in sched} == {traffic.DEFAULT_TENANT}


@pytest.mark.parametrize("tenants,shares", [
    ({"n": 5, "zipf_s": 1.1}, 1.0 / np.arange(1, 6) ** 1.1),
    ({"ids": ["chat", "batch", "eval"], "shares": [0.6, 0.3, 0.1]},
     np.array([0.6, 0.3, 0.1])),
], ids=["zipf", "ids"])
def test_tenant_shares_follow_the_file(tenants, shares):
    mix = dict(MIX, tenants=tenants)
    plain = traffic.schedule(MIX, 2**31 + 977, 45, 49155)
    for seed in (2**31 + 977, 4):
        sched = traffic.schedule(mix, seed, 45, 49155)
        want = dict(zip(traffic.tenants(mix), shares / shares.sum()))
        got = collections.Counter(a.tenant for a in sched)
        assert set(got) == set(want)
        for t, share in want.items():
            # exact proportions: off by less than one request
            assert abs(got[t] - share * len(sched)) < 1.0
            # and within sampling error over any stretch of 60 requests
            part = sum(a.tenant == t for a in sched[:60])
            assert abs(part - 60 * share) <= 3 * (60 * share) ** 0.5 + 1
    # the tenants' draws leave the rest of the schedule as it was
    sched = traffic.schedule(mix, 2**31 + 977, 45, 49155)
    assert _digest(sched) == _digest(plain)


def test_malformed_tenants_are_refused():
    for bad in ({"ids": ["a", "a"], "shares": [1, 1]},
                {"ids": ["a"], "shares": [0]},
                {"ids": ["a", "b"], "shares": [1]},
                {"n": 0, "zipf_s": 1.0}):
        with pytest.raises(ValueError):
            traffic.tenants(dict(MIX, tenants=bad))
