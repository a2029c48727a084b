"""The serving layout from the files: partitions from the configuration's
``serving`` block, tenants from the traffic file."""
import json
import os
import subprocess
import sys

from bench import run as R

from .conftest import ROOT, make_bench

SEED = 2**31 + 41


class PartitionSink(R.EventSink):
    """The harness's sink, noting each event's partition and tenant."""

    seen = []

    def on_event(self, ev):
        if ev.kind in ("prefill", "decode"):
            PartitionSink.seen.append((ev.kind, ev.partition, ev.tenant))
        super().on_event(ev)


def _two_partitions(tmp_path):
    return make_bench(tmp_path, "tiny-2part.json", "tiny-zipf-traffic.json")


def test_two_partitions_three_tenants_on_the_cpu(tmp_path, monkeypatch):
    """Logical partitions on one device: the run is correct, both
    partitions decode, every tenant is served, and the per-layer readers
    see the decode events of both."""
    root, cell = _two_partitions(tmp_path)
    PartitionSink.seen = []
    monkeypatch.setattr(R, "EventSink", PartitionSink)
    res = R.run_cell(cell, SEED, 2.0, True, bench=root, require_chip=False)
    assert res["correct"], res["checks"]
    seen = PartitionSink.seen
    assert {p for k, p, _ in seen if k == "decode"} == {0, 1}
    assert {t for k, _, t in seen if k == "prefill"} >= {"t0", "t1", "t2"}
    assert {"decode_step_ms", "batch_occupancy"} <= set(res["metrics"])


def test_one_partition_to_a_device(tmp_path):
    """With a device for each partition, each runs on its own: two CPU
    devices stand in for two chips."""
    root, cell = _two_partitions(tmp_path)
    script = f"""
import json, sys
from pathlib import Path
from bench import run as R
from bench.tests.test_layout import PartitionSink
built = R.build_runtime
devices = []

def build(*args, **kw):
    runtime = built(*args, **kw)
    devices.extend(str(runtime._device(p)) for p in runtime.partitions)
    return runtime

R.EventSink, R.build_runtime = PartitionSink, build
cell = dict(json.loads(sys.argv[1]), chips=2)
res = R.run_cell(cell, {SEED}, 2.0, False, bench=Path(sys.argv[2]),
                 require_chip=False)
print(json.dumps({{"correct": res["correct"], "devices": devices,
                  "decode": sorted({{p for k, p, _ in PartitionSink.seen
                                    if k == "decode"}})}}))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    out = subprocess.run([sys.executable, "-c", script, json.dumps(cell),
                          str(root)], cwd=ROOT, env=env, capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["correct"] and got["decode"] == [0, 1]
    assert len(set(got["devices"])) == 2 and "None" not in got["devices"]
