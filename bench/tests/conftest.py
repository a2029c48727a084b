"""Shared fixtures: a tiny benchmark directory that runs on the CPU."""
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

DATA = Path(__file__).resolve().parent / "data"
BENCH = ROOT / "bench"

E2E = [("ttft_p90_ms", "ms"), ("itl_p95_ms", "ms"),
       ("output_tokens_per_s", "tokens/s"), ("setup_s", "s")]


def make_bench(tmp_path, config: str, traffic: str, archs=(),
               references=()):
    """(bench dir, cell) for the configuration and traffic files
    ``config`` and ``traffic`` of ``data/``, with the architecture and
    reference modules (data file, name) beside them."""
    root = tmp_path / "bench"
    (root / "configs").mkdir(parents=True)
    (root / "traffic").mkdir()
    (root / "archs").mkdir()
    shutil.copytree(BENCH / "metrics", root / "metrics")
    shutil.copytree(BENCH / "references", root / "references")
    for kind, files in (("archs", archs), ("references", references)):
        for src, name in files:
            shutil.copy(DATA / src, root / kind / f"{name}.py")
    name = config.removesuffix(".json")
    shutil.copy(DATA / config, root / "configs" / f"{name}.json")
    shutil.copy(DATA / traffic, root / "traffic" / f"{name}.json")
    names = sorted(p.stem for p in (BENCH / "metrics").glob("*.py"))
    cell = {"name": f"{name}.chat", "config": name, "traffic": name,
            "chips": 1,
            "end_to_end": [{"name": n, "unit": u} for n, u in E2E],
            "per_layer": [{"name": n, "unit": "x"} for n in names]}
    return root, cell


@pytest.fixture
def tiny_bench(tmp_path):
    """(bench dir, cell) for a two-layer MoE under a light mix."""
    return make_bench(tmp_path, "tiny.json", "tiny-traffic.json")
