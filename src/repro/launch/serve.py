"""Serving driver: batched requests, continuous batching, technique switches.

CPU-runnable with ``--reduced``; demonstrates the paper-§9.2 serving levers:
FP8 weights, 2:4-packed weights (bandwidth win in the memory-bound decode
regime), batch-slot occupancy — and the serving control plane
(runtime/server.py): multi-tenant admission, spatial partitions with
per-partition execution policies, and live tenant migration.

The canonical way to configure the control plane is a serialized
``ServingSpec`` (``--spec spec.json``). The legacy flag cluster
(``--partitions/--placement/--adaptive-quota/--admission/…``) is kept as
shorthand that *builds* a spec — ``--save-spec out.json`` writes the
effective spec so a flag invocation can be promoted to a declarative one.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --reduced \
      --requests 8 --max-new 16 --precision fp8
  PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --reduced \
      --requests 8 --tenants 4 --admission fair_quantum
  PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --reduced \
      --requests 8 --tenants 4 --partitions 2 --placement load_aware \
      --adaptive-quota --migrate
  PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --reduced \
      --requests 8 --tenants 4 --spec myspec.json
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np


def build_spec(args, policy):
    """The legacy flag cluster as a :class:`ServingSpec` (the shorthand
    path; ``--spec`` supersedes it)."""
    from repro.runtime.server import (
        MigrationSpec, PartitionSpec, ServingSpec)
    quota = "adaptive" if args.adaptive_quota else None
    return ServingSpec(
        partitions=tuple(
            PartitionSpec(admission=args.admission, quota=quota)
            for _ in range(max(1, args.partitions))),
        placement=args.placement,
        batch_slots=args.slots,
        max_len=args.max_len,
        temperature=args.temperature,
        seed=args.seed,
        policy=policy,
        migration=MigrationSpec(enabled=args.migrate),
        # paged/overlap flags default for callers driving build_spec with
        # a legacy (pre-paging / pre-lane) namespace
        paged=getattr(args, "paged", False),
        page_size=getattr(args, "page_size", 16),
        pages=getattr(args, "pages", None),
        overlap=not getattr(args, "no_overlap", False),
        metrics=getattr(args, "metrics_out", None) is not None,
        controller=_parse_controller(getattr(args, "controller", None)))


def _parse_controller(arg):
    from repro.runtime.controller import ControllerSpec
    return ControllerSpec.parse(arg)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--precision", default=None, choices=[None, "bf16", "fp8"])
    ap.add_argument("--backend", default=None,
                    choices=[None, "ref", "jnp", "pallas", "pallas_sparse24"],
                    help="matmul backend (kernels/registry.py)")
    ap.add_argument("--policy", default=None,
                    help="execution-policy spec ('fp8:sparse24:pallas'), or "
                         "'auto' to resolve via the occupancy advisor "
                         "(paper §9.2) from slots/d_model/d_ff")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tenants", type=int, default=1,
                    help="number of tenant queues; >1 routes through the "
                         "serving control plane / StreamScheduler")
    ap.add_argument("--spec", default=None, metavar="PATH",
                    help="serialized ServingSpec (runtime/server.py); "
                         "supersedes the partition/placement/admission/"
                         "quota shorthand flags")
    ap.add_argument("--save-spec", default=None, metavar="PATH",
                    help="write the effective ServingSpec as JSON (promote "
                         "a flag invocation to a declarative spec)")
    ap.add_argument("--admission", default="fair_quantum",
                    choices=["fifo", "round_robin", "fair_quantum"],
                    help="[shorthand] multi-tenant admission policy")
    ap.add_argument("--partitions", type=int, default=1,
                    help="[shorthand] spatial sub-mesh partitions; >1 "
                         "serves tenants through the ServingRuntime "
                         "control plane (runtime/server.py)")
    ap.add_argument("--placement", default="spread",
                    choices=["packed", "spread", "load_aware"],
                    help="[shorthand] tenant->partition routing policy")
    ap.add_argument("--adaptive-quota", action="store_true",
                    help="[shorthand] re-derive per-tenant fair_quantum "
                         "slot caps online from Tracer.tenant_percentiles()")
    ap.add_argument("--migrate", action="store_true",
                    help="[shorthand] enable live tenant migration (the "
                         "load_aware re-route path; see MigrationSpec)")
    ap.add_argument("--paged", action="store_true",
                    help="paged serving cache (core/paging.py): per-slot "
                         "page tables over a shared pool + fused paged "
                         "flash-decode; greedy output is token-identical "
                         "to the dense path")
    ap.add_argument("--page-size", type=int, default=16,
                    help="token positions per cache page (must divide "
                         "--max-len)")
    ap.add_argument("--pages", type=int, default=None,
                    help="physical pool size in pages (default: dense-"
                         "equivalent capacity, slots * max_len/page_size)")
    ap.add_argument("--no-overlap", action="store_true",
                    help="disable lane overlap: partitions step through "
                         "the serial loop instead of OverlapPlanner-paired "
                         "concurrent dispatch (token streams are identical "
                         "either way)")
    ap.add_argument("--telemetry", action="store_true",
                    help="record per-op/per-tenant events to a Tracer and "
                         "print the observatory summary at exit")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the metrics-registry snapshot at exit "
                         "(.json, or Prometheus text for .prom/.txt); "
                         "implies the metrics plane (runtime/metrics.py)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="export the run's telemetry as Chrome trace_event "
                         "JSON (runtime/traceview.py) — open in "
                         "chrome://tracing or https://ui.perfetto.dev")
    ap.add_argument("--slo", default=None,
                    help="SLO class for every shorthand tenant "
                         "('latency:12', 'latency:0.05@wall_s', "
                         "'throughput:1.5', 'batch:0.9'); reports and "
                         "metrics surface per-tenant attainment")
    ap.add_argument("--autotune", action="store_true",
                    help="load the persistent autotune artifact "
                         "(launch/profile.py) and resolve policies from "
                         "calibrated thresholds")
    ap.add_argument("--controller", default=None, nargs="?", const="on",
                    metavar="SPEC",
                    help="SLO closed loop (runtime/controller.py): bare "
                         "flag for defaults, or 'interval=2,low=0.85,"
                         "hold=4' knobs; freezes batch-class tenants / "
                         "boosts slot caps while a latency-class tenant "
                         "misses its SLO")
    ap.add_argument("--workload", default=None, metavar="TRACE",
                    help="replay a WorkloadTrace JSON (launch/loadgen.py "
                         "--save-trace) through the runtime instead of "
                         "the synthetic --requests stream; tenants and "
                         "SLOs come from the trace spec")
    args = ap.parse_args()

    from repro.configs import get_arch, get_reduced
    from repro.core import autotune, execution as ex
    from repro.models import init_params
    from repro.models.layers import RuntimeCfg
    from repro.runtime import telemetry
    from repro.runtime.serve_loop import Request, ServeSession
    from repro.runtime.scheduler import StreamScheduler
    from repro.runtime.server import ServingRuntime, ServingSpec

    if args.autotune:
        store = autotune.install()
        print(f"[serve] autotune artifact "
              f"{'loaded: ' + store.path if store else 'not found'}")
    # --metrics-out / --trace-out need an event stream even without
    # --telemetry's summary printing
    want_tracer = args.telemetry or args.metrics_out or args.trace_out
    tracer = telemetry.Tracer() if want_tracer else None
    if tracer is not None:
        telemetry.set_tracer(tracer)    # observe trace-time matmul events

    cfg = get_reduced(args.arch) if args.reduced else get_arch(args.arch)
    if args.precision:
        cfg = dataclasses.replace(cfg, precision=args.precision)

    policy = None
    if args.policy == "auto":
        policy = "auto"        # ServeSession resolves, honoring auto_backend
    elif args.policy or args.backend:
        base = ex.ExecutionPolicy(
            precision=cfg.precision,
            sparsity="sparse24" if cfg.sparsity_24 else "dense")
        policy = ex.parse_policy(args.policy or "", base=base)
        if args.backend:
            policy = dataclasses.replace(policy, backend=args.backend)

    if args.spec:
        spec = ServingSpec.load(args.spec)
        print(f"[serve] spec loaded: {args.spec} "
              f"({spec.n_partitions} partitions, {spec.placement}, "
              f"migration={'on' if spec.migration.enabled else 'off'})")
        if args.metrics_out and not spec.metrics:
            spec = dataclasses.replace(spec, metrics=True)
        if args.controller:
            spec = dataclasses.replace(
                spec, controller=_parse_controller(args.controller))
    else:
        spec = build_spec(args, policy)
    if args.save_spec:
        print(f"[serve] spec written: {spec.save(args.save_spec)}")

    rt = RuntimeCfg(ssm_chunk=32)
    params = init_params(jax.random.PRNGKey(args.seed), cfg)

    rng = np.random.default_rng(args.seed)
    requests = []
    for uid in range(args.requests):
        prompt = rng.integers(0, cfg.vocab_size,
                              size=(args.prompt_len,)).astype(np.int32)
        requests.append(Request(uid=uid, prompt=prompt,
                                max_new=args.max_new))

    use_runtime = (args.spec is not None or spec.n_partitions > 1
                   or spec.migration.enabled or args.workload is not None
                   or args.controller is not None)
    if use_runtime:
        # the serving control plane: one runtime from one spec — per-
        # partition policies, routed tenants, optional live migration
        runtime = ServingRuntime(
            params, cfg, spec, rt=rt,
            session_kw={"auto_backend": args.backend,
                        "verbose_policy": True})
        # timed region starts AFTER construction: session setup (policy
        # resolution, sparse24 pre-pack, cache alloc) must not pollute
        # the reported serving tok/s
        t0 = time.time()
        if args.workload:
            from repro.runtime.workload import WorkloadTrace, run_trace
            wtrace = WorkloadTrace.load(args.workload)
            print(f"[serve] workload trace: {args.workload} "
                  f"({len(wtrace.events)} arrivals / "
                  f"{len(wtrace.tenant_ids())} tenants over "
                  f"{wtrace.steps} steps)")
            done = run_trace(runtime, wtrace)
            args.requests = len(wtrace.events)
        else:
            tenant_ids = [t.id for t in spec.tenants]
            if not tenant_ids:
                tenant_ids = [f"tenant{i}"
                              for i in range(max(args.tenants, 1))]
                for tid in tenant_ids:
                    part = runtime.add_tenant(tid, slo=args.slo)
                    print(f"[serve] {tid} -> partition {part} "
                          f"({spec.placement})")
            for uid, req in enumerate(requests):
                runtime.submit(tenant_ids[uid % len(tenant_ids)], req)
            done = runtime.drain()
        if runtime.controller is not None:
            counts = runtime.controller.counts()
            print(f"[serve] controller: checks "
                  f"{runtime.controller.checks} · "
                  + ", ".join(f"{a}:{n}" for a, n in counts.items()))
        print(runtime.report().summary())
        if args.telemetry:
            print(runtime.merged_tracer().summary())
            # the ambient tracer holds the trace-time per-op events
            # (matmul/resolve) the per-partition tracers don't see
            print(tracer.summary())
        if args.metrics_out and runtime.metrics is not None:
            print(f"[serve] metrics written: "
                  f"{runtime.metrics.save(args.metrics_out)}")
        if args.trace_out:
            from repro.runtime import traceview
            merged = telemetry.Tracer.merge(*runtime.tracers, tracer)
            print(f"[serve] trace written: "
                  f"{traceview.export_chrome_trace(merged, args.trace_out)}"
                  " (open in chrome://tracing or ui.perfetto.dev)")
        dt = time.time() - t0
        total_new = sum(len(r.out) for r in done)
        print(f"[serve] {len(done)}/{args.requests} requests, "
              f"{total_new} tokens in {dt:.1f}s "
              f"({total_new / max(dt, 1e-9):.1f} tok/s aggregate)")
        return 0

    sess = ServeSession(params, cfg, batch_slots=args.slots,
                        max_len=args.max_len, rt=rt,
                        temperature=args.temperature, seed=args.seed,
                        policy=policy, auto_backend=args.backend,
                        verbose_policy=True, telemetry=tracer,
                        paged=args.paged, page_size=args.page_size,
                        pages=args.pages)
    registry = None
    if args.metrics_out:
        from repro.runtime.metrics import MetricsSink
        registry = MetricsSink().attach(tracer).registry
    if args.paged:
        print(f"[serve] paged cache: page_size={sess.page_size} "
              f"pages={sess.pages}")
    t0 = time.time()

    if args.tenants > 1:
        # multi-tenant: requests dealt round-robin over tenant queues. The
        # session policy becomes each tenant's slot quota only when its
        # stream budget was actually chosen (advisor-resolved via 'auto',
        # or an explicit streams= token) — a policy built just to pick a
        # backend carries the default streams=1 and would silently cap
        # every tenant to one slot.
        quota = "adaptive" if args.adaptive_quota else None
        sched = StreamScheduler(sess, admission=args.admission,
                                tracer=tracer, quota=quota)
        tpol = None
        if isinstance(sess.policy, ex.ExecutionPolicy) and (
                args.policy == "auto" or "streams=" in (args.policy or "")):
            tpol = sess.policy
        for i in range(args.tenants):
            sched.add_tenant(f"tenant{i}", policy=tpol, slo=args.slo)
        for uid, req in enumerate(requests):
            sched.submit(f"tenant{uid % args.tenants}", req)
        done = sched.run()
        print(sched.report().summary())
    else:
        for req in requests:
            sess.submit(req)
        done = sess.run()
    dt = time.time() - t0
    total_new = sum(len(r.out) for r in done)
    print(f"[serve] {len(done)}/{args.requests} requests, "
          f"{total_new} tokens in {dt:.1f}s "
          f"({total_new / max(dt, 1e-9):.1f} tok/s aggregate)")
    for r in done[:4]:
        print(f"  req {r.uid}: {len(r.out)} new tokens, first 8: {r.out[:8]}")
    if args.telemetry and tracer is not None:
        print(tracer.summary())
    if registry is not None:
        print(f"[serve] metrics written: {registry.save(args.metrics_out)}")
    if args.trace_out and tracer is not None:
        from repro.runtime import traceview
        print(f"[serve] trace written: "
              f"{traceview.export_chrome_trace(tracer, args.trace_out)}"
              " (open in chrome://tracing or ui.perfetto.dev)")
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    raise SystemExit(main())
