"""Calibration-sweep CLI: measure this machine's execution behavior,
persist the autotune artifact, print a characterization report.

Runs a short occupancy sweep (Fig-2 methodology) and tile-latency probe
(Table-3 methodology), folds the measurements into the persistent
:class:`repro.core.autotune.AutotuneStore`, re-derives the FP8-demotion
occupancy threshold from the samples, and shows how ``resolve_policy``'s
decisions change under the calibrated advisor.

  PYTHONPATH=src python -m repro.launch.profile --quick
  PYTHONPATH=src python -m repro.launch.profile --artifact-dir /tmp/cal
  PYTHONPATH=src python -m repro.launch.profile --reset --quick

The artifact (``autotune.json``) lives in ``$REPRO_AUTOTUNE_DIR`` or
``benchmarks/artifacts/autotune``; every later run that calls
``autotune.install()`` (or ``launch/{train,serve}.py --autotune``) picks
it up, so one calibration permanently informs policy resolution.
"""
from __future__ import annotations

import argparse
import time


def build_argparser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="CPU-sized sweep (fewer shapes, 1 timing iter); "
                         "seconds instead of minutes")
    ap.add_argument("--artifact-dir", default=None,
                    help="override the autotune artifact directory "
                         "($REPRO_AUTOTUNE_DIR / benchmarks/artifacts/"
                         "autotune)")
    ap.add_argument("--reset", action="store_true",
                    help="discard any existing artifact before measuring")
    ap.add_argument("--iters", type=int, default=None,
                    help="timing iterations per point (default: 1 quick, "
                         "3 full)")
    ap.add_argument("--no-save", action="store_true",
                    help="measure and report only; leave the artifact "
                         "untouched")
    return ap


def main(argv=None) -> int:
    args = build_argparser().parse_args(argv)

    from repro.core import autotune, concurrency as cc, execution as ex
    from repro.core.characterization import (latency_probe, occupancy_sweep,
                                             occupancy_threshold)
    from repro.runtime import telemetry

    store = autotune.AutotuneStore(args.artifact_dir)
    if args.reset:
        store.reset()
        print(f"[profile] reset artifact at {store.path}")
    elif store.load():
        print(f"[profile] merged existing artifact "
              f"({len(store.blocks)} blocks, {len(store.samples)} samples)")

    tracer = telemetry.Tracer()
    prev = telemetry.set_tracer(tracer)
    iters = args.iters or (1 if args.quick else 3)
    n_cores = cc.detect_core_count()
    t0 = time.time()
    try:
        if args.quick:
            tile_counts, k = (1, 2, 4), 128
            precisions = ("bf16", "fp8")
            tile_shapes = ((128, 128, 128), (128, 128, 256))
            chain = 2
        else:
            tile_counts, k = (1, 2, 4, 8, 16), 256
            precisions = ("fp32", "bf16", "fp8")
            tile_shapes = ((128, 128, 128), (256, 256, 128),
                           (128, 128, 256), (256, 256, 256))
            chain = 8

        print(f"[profile] occupancy sweep: tiles={tile_counts} "
              f"precisions={precisions} iters={iters}")
        occ = occupancy_sweep(tile_counts=tile_counts, k=k, n=k,
                              precisions=precisions, iters=iters)
        store.add_records(occ)

        print(f"[profile] tile-latency probe: {len(tile_shapes)} shapes, "
              f"chain={chain}")
        lat = latency_probe(tile_shapes=tile_shapes, precisions=precisions,
                            chain=chain, iters=iters)
        ex.seed_cache_from_records(lat)      # refine this process too
        store.add_records(lat)
    finally:
        telemetry.set_tracer(prev)

    thresholds = store.calibrate(n_cores=n_cores)
    saved = None if args.no_save else store.save()

    # ---- report ----------------------------------------------------------
    print(f"\n[profile] characterization ({time.time() - t0:.1f}s, "
          f"n_cores={n_cores})")
    th90 = occupancy_threshold(occ, frac=0.9)
    print("  tiles to 90% of best throughput: " + ", ".join(
        f"{p}={t}" for p, t in sorted(th90.items())))
    if "knee_tiles" in thresholds:
        print(f"  measured FP8 knee: {thresholds['knee_tiles']:g} tiles "
              f"-> demote below fill {thresholds['demote_below_fill']:.4g}"
              f"x cores (prior: "
              f"{cc.OccupancyAdvisor.BF16_TILE_THRESHOLD}x)")
    else:
        print("  no comparable fp8/bf16 samples; thresholds keep priors")
    print(f"  store: {len(store.blocks)} block entries, "
          f"{len(store.samples)} samples")
    print("  " + tracer.summary(n_cores=n_cores).replace("\n", "\n  "))

    # resolve_policy before/after, at the largest measured occupancy step
    cal = store.make_advisor(n_cores=n_cores)
    prior = cc.OccupancyAdvisor(n_cores=n_cores)
    demo_tiles = int(thresholds.get("knee_tiles", n_cores))
    for label, tiles in (("below-knee", max(1, demo_tiles // 2)),
                         ("at-knee", demo_tiles)):
        m = 128 * max(1, tiles)
        p0 = ex.resolve_policy(m, 4096, 128, precision="fp8", advisor=prior)
        p1 = ex.resolve_policy(m, 4096, 128, precision="fp8", advisor=cal)
        flip = "  <-- calibration changed the decision" \
            if p0.precision != p1.precision else ""
        print(f"  resolve[{label}, {tiles} tiles]: prior={p0.spec()} "
              f"calibrated={p1.spec()}{flip}")
    if saved:
        print(f"[profile] artifact written: {saved}")
    else:
        print("[profile] --no-save: artifact not written")
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    raise SystemExit(main())
