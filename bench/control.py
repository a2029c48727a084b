#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, on the chip, in one process.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 30 \\
        [--out control.json]

For each seed it serves the cell's traffic through the harness's own path
(``run.measure``: the same weights, runtime, warm-up, pre-roll and window,
at the cell's load) and takes the same sample of finished requests as a
benchmark run. On that sample it reads:

* ``program``: the numbers a run compares (``run.gap_readings``): the
  widest gap by which a served token's logit lies below the float32
  reference's best at its position, the mean of those gaps, and the share
  of served tokens that are not the reference's best;
* ``control``: the same numbers for the tokens that the reference computed
  in float8 (``fp8=True``) puts first at each of those positions, the
  precision step below the bf16 the configuration states.

Both go through the harness's own comparison (``run.compare``) against the
configuration's limits: ``program_correct`` has to come out true and
``control_correct`` false on every seed. Each limit lies between the
largest ``program`` reading over a dozen seeds or more and the smallest
``control`` reading. The benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np  # noqa: E402

from bench import run as R  # noqa: E402


def control_gaps(fwd, fwd_low, params, traffic: dict, sample):
    """Per request: the reference's gap for the low-precision argmax at
    the positions a run compares."""
    T, n_max = R.ref_length(traffic), traffic["output"]["max"]
    out = []
    for r in sample:
        toks, rows, _, n = R.reference_inputs(r, T, n_max)
        low = np.asarray(fwd_low(params, toks, rows)).argmax(-1)
        g = np.asarray(R.gap_of(fwd(params, toks, rows), low))
        out.append(g[R.compared_positions(n)])
    return out


def readings(cell: dict, seeds, seconds: float, require_chip: bool = True,
             bench: Path = R.BENCH):
    import jax
    if require_chip:
        devs, _ = R.check_device(int(cell.get("chips", 1)))
    else:
        devs = jax.devices()
    conf = R.bmodel.load_config(cell["config"], bench)
    traffic = R.btraffic.load_traffic(cell["traffic"], bench)
    ref = R.load_reference(conf, bench)
    fwd, fwd_low = ref.make_forward(conf), ref.make_forward(conf, fp8=True)
    compiles = R.CompileCounter()
    rows = []
    for seed in seeds:
        m = R.measure(conf, traffic, seed, seconds, False,
                      R.cell_devices(cell, devs), compiles)
        finished = [r for r in m.recs if r.req.done]
        wrong = sum(len(r.req.out) != r.req.max_new for r in finished)
        sample = R.pick_sample(finished, seed)
        t = time.perf_counter()
        params = R.bmodel.base_weights(conf, seed)
        prog = R.gap_readings(R.served_gaps(fwd, params, traffic, sample))
        ctrl = R.gap_readings(
            control_gaps(fwd, fwd_low, params, traffic, sample))
        del params
        row = {"seed": seed, "requests": len(sample),
               "longest": max(len(r.req.out) for r in sample),
               "program": prog, "control": ctrl,
               "program_correct": R.compare(prog, wrong, conf["check"])[1],
               "control_correct": R.compare(ctrl, 0, conf["check"])[1]}
        rows.append(row)
        R.log(f"seed {seed}: program {prog} correct "
              f"{row['program_correct']}; control {ctrl} correct "
              f"{row['control_correct']}; over {row['requests']} requests "
              f"(longest {row['longest']}); reference "
              f"{time.perf_counter() - t:.1f} s")
        del m
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    R.setup_jax()
    cell = R.load_cell(args.workload)
    rows = readings(cell, [int(s) for s in args.seeds.split(",")],
                    args.seconds)
    names = ("logit_gap", "mean_gap", "mismatch_share")
    summary = {"workload": args.workload, "seconds": args.seconds,
               "program_max": {n: max(r["program"][n] for r in rows)
                               for n in names},
               "control_min": {n: min(r["control"][n] for r in rows)
                               for n in names},
               "program_correct": all(r["program_correct"] for r in rows),
               "control_correct": any(r["control_correct"] for r in rows),
               "rows": rows}
    text = json.dumps(summary)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(text)
    print(text)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
