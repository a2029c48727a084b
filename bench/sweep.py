#!/usr/bin/env python3
"""Find a mix's knee on the chip: the harness's window at several rates.

    python3 bench/sweep.py --workload <cell> --rates 1.0,1.15,1.3 \\
        --seconds 40 --seed 7 [--out sweep.json]

Runs the cell's traffic at each rate in one process, through the same path
as a benchmark run (``run.measure``), and prints, per rate, the requests
queued when the window opened and when it closed, the requests due and
completed in the window, the end-to-end metrics and the batch occupancy.
The knee is the highest rate at which the queue does not grow over the
window; a cell's traffic file then states its rate as a number. The
benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import run as R  # noqa: E402


def sweep(cell: dict, rates, seconds: float, seed: int):
    devs, _ = R.check_device(int(cell.get("chips", 1)))
    conf = R.bmodel.load_config(cell["config"])
    traffic = R.btraffic.load_traffic(cell["traffic"])
    compiles = R.CompileCounter()
    rows = []
    for rate in rates:
        mix = dict(traffic, rate_per_s=rate)
        m = R.measure(conf, mix, seed, seconds, False,
                      R.cell_devices(cell, devs), compiles)
        e2e = R.end_to_end(m.recs, m.w0, m.w1)
        done = sum(m.w0 <= r.due < m.w1 and r.req.done for r in m.recs)
        occ = [n for s, e, n in m.sink.decode if m.w0 <= s and e <= m.w1]
        row = dict(rate=rate, queued_open=m.queued[0],
                   queued_close=m.queued[1], due=e2e["n_due"],
                   completed=done, occupancy=(
                       sum(occ) / len(occ) / conf["serving"]["batch_slots"]
                       if occ else None),
                   **{k: e2e[k] for k in ("ttft_p90_ms", "itl_p95_ms",
                                          "output_tokens_per_s")})
        rows.append(row)
        R.log("sweep " + json.dumps(row))
        del m
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    R.setup_jax()
    rows = sweep(R.load_cell(args.workload),
                 [float(r) for r in args.rates.split(",")], args.seconds,
                 args.seed)
    text = json.dumps({"workload": args.workload, "rows": rows})
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(text)
    print(text)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
