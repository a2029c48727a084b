#!/usr/bin/env python3
"""Bring-up smoke: serve granite-moe-3b-a800m at published widths on a TPU.

The default run drives the serving main path once on one chip, through
``ServingRuntime`` built from a ``ServingSpec``, with random weights from
``--seed``:

* two partitions on the one chip, ``bf16:dense:jnp`` and
  ``fp8:sparse24:pallas_sparse24`` (the second runs the fp8 and packed-2:4
  Pallas kernels);
* a paged KV cache of 8 slots x 2048 positions per partition;
* two tenants, one pinned to each partition, sending 16 requests with
  prompts of 128 and 512 tokens and 32 new tokens each.

It fails unless every request completes, the Pallas partition's decode
step holds Pallas kernels (``tpu_custom_call``) with no jnp fallback, each
served first token is the argmax of its partition's own prefill logits, and
on the model's first layer those logits and first tokens agree with the
float32 ``ref`` backend at ``Precision.HIGHEST`` (see ``ORACLE_LAYERS``).
Timings it prints are a bring-up reading, not a benchmark.

``--chips 4`` runs only the multi-chip phase: four partitions, one per chip,
``load_aware`` placement with live migration, compared with the same
requests served by one partition on chip 0.

The last line of stdout is one JSON object naming the device. Without a
TPU (and without ``--reduced``) the script exits non-zero and prints none.

  python chip_smoke.py                # one TPU chip
  python chip_smoke.py --chips 4      # four chips of one host
  python chip_smoke.py --reduced      # reduced config, any platform (CPU)
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

ARCH = "granite-moe-3b-a800m"
POLICIES = ("bf16:dense:jnp", "fp8:sparse24:pallas_sparse24")
CHIPS4_POLICY = "bf16:dense:jnp"

# Relative L2 error of prefill logits against the float32 ``ref`` oracle at
# Precision.HIGHEST, taken on the first ORACLE_LAYERS layers of the model
# (published widths, the same weights). The served path keeps bf16
# activations between ops (2^-8 = 3.9e-3 relative rounding each), and the
# chip's default matmul precision also rounds the operands of f32 einsums
# (attention scores and probabilities, MoE combine) to bf16. A random-weight
# MoE decoder amplifies any such difference by about 3.7x per layer, through
# top-k routing flips and attention: on a TPU v5e the bf16 partition
# measured 5.7e-3 after one layer, 0.30 after four and 1.16 after all 32,
# while with every XLA dot at HIGHEST the jnp and ref backends agreed
# bit for bit. So the full depth is printed but not bounded; one layer runs
# every kernel of the policy (attention projections, experts, LM head) at
# full width. The reduced CPU rehearsal, where only the bf16 roundings act,
# measured 4.3e-3 after one layer (bf16 partition; 7.0e-8 for fp8 + 2:4,
# whose kernels and oracle quantize alike there). A wrong kernel, layout or
# scale gives errors of order 1.
ORACLE_LAYERS = 1
REL_L2_BOUND = 5e-2
# Four-chip phase: the same program on the same chip type, so prefill
# logits of one request must not depend on which chip served it.
CHIPS4_REL_L2_BOUND = 1e-6
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass(frozen=True)
class Shape:
    """Serving geometry of one run."""
    slots: int
    max_len: int
    prompt_lens: tuple
    requests: int
    max_new: int
    check_prompts: int


FULL = Shape(slots=8, max_len=2048, prompt_lens=(128, 512), requests=16,
             max_new=32, check_prompts=3)
REDUCED = Shape(slots=8, max_len=128, prompt_lens=(16, 48), requests=16,
                max_new=8, check_prompts=2)


class SmokeFailure(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


class CompileLog:
    """Backend compile seconds per jitted function, from JAX's monitoring
    events (persistent-cache hits count as the time they took)."""

    def __init__(self):
        self.secs = collections.defaultdict(float)

    def __call__(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.secs[kw.get("fun_name", "?")] += duration

    def summary(self) -> str:
        big = sorted(self.secs.items(), key=lambda kv: -kv[1])
        return ", ".join(f"{k}={v:.1f}" for k, v in big if v >= 0.05)


def hbm() -> str:
    """Device 0's bytes in use now and at peak, where JAX reports them."""
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    if "bytes_in_use" not in stats:
        return "HBM not reported"
    return (f"HBM in use {stats['bytes_in_use'] / 1e9:.2f} GB, peak "
            f"{stats.get('peak_bytes_in_use', 0) / 1e9:.2f} GB")


def make_requests(cfg, shape: Shape, lens, seed: int, uid0: int = 0):
    """Prompt lengths cycle in pairs (a, a, b, b, ...), so two tenants
    taking alternate requests each get every length."""
    import numpy as np
    from repro.runtime.serve_loop import Request
    rng = np.random.default_rng(seed)
    return [Request(uid=uid0 + i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        size=(lens[i // 2 % len(lens)],))
                    .astype(np.int32),
                    max_new=shape.max_new)
            for i in range(shape.requests)]


def init_params(cfg, seed: int):
    import jax
    from repro.models import init_params as init
    t0 = time.perf_counter()
    params = jax.jit(init, static_argnums=1)(jax.random.PRNGKey(seed), cfg)
    jax.block_until_ready(params)
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    nbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    log(f"model {cfg.name}: {cfg.num_layers}L d_model={cfg.d_model} "
        f"experts={cfg.num_experts} top{cfg.experts_top_k}, {n} params "
        f"({nbytes / 1e9:.2f} GB), init {time.perf_counter() - t0:.1f} s; "
        f"{hbm()}")
    return params


def prefill_logits(sess, prompt):
    """The partition's own jitted prefill on its own device: (Vp,) f32."""
    import numpy as np
    with sess._policy_scope():
        logits, _ = sess.prefill_fn(sess.params,
                                    sess._put(np.asarray(prompt)[None]))
    return np.asarray(logits[0], np.float64)


def rel_l2(got, want) -> float:
    import numpy as np
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def step_walls(runtime, since: float):
    """Median prefill wall per prompt length and median decode-step wall
    per partition, from the partition tracers' events after ``since``."""
    import numpy as np
    rows = []
    for i, tr in enumerate(runtime.tracers):
        pre = collections.defaultdict(list)
        for ev in tr.events("prefill"):
            if ev.t >= since:
                pre[ev.m].append(ev.wall_s)
        dec = [ev.wall_s for ev in tr.events("decode") if ev.t >= since]
        parts = [f"prefill[{m}]={np.median(v) * 1e3:.1f}ms(n={len(v)})"
                 for m, v in sorted(pre.items())]
        if dec:
            parts.append(f"decode={np.median(dec) * 1e3:.1f}ms"
                         f"(n={len(dec)})")
        rows.append(f"p{i} {runtime.policy_key(i)}: " + " ".join(parts))
    return rows


def first_layers(params, n: int):
    """The model cut to its first ``n`` layers (stacked along axis 0)."""
    import jax
    return dict(params, layers=jax.tree_util.tree_map(lambda a: a[:n],
                                                      params["layers"]))


def check_against_oracle(runtime, cfg, done_by_uid, prompts_by_part):
    """Prefill logits and first tokens of each partition's policy against
    the same policy on the float32 ``ref`` backend at HIGHEST: bounded on
    the first ORACLE_LAYERS layers, printed at full depth."""
    import jax
    import numpy as np
    from repro.core import execution as ex
    from repro.models.layers import RuntimeCfg
    from repro.runtime.serve_loop import make_prefill_step
    V = cfg.vocab_size
    cut = dataclasses.replace(cfg, num_layers=ORACLE_LAYERS)
    for i, sess in enumerate(runtime.sessions):
        pol = sess.policy
        ref_pol = dataclasses.replace(pol, backend="ref")
        cut_params = first_layers(sess.params, ORACLE_LAYERS)
        cut_fn = jax.jit(make_prefill_step(cut, RuntimeCfg(), pol))
        cut_ref = jax.jit(make_prefill_step(cut, RuntimeCfg(), ref_pol))
        full_ref = jax.jit(make_prefill_step(cfg, RuntimeCfg(), ref_pol))

        def logits(fn, params, prompt, highest=False):
            prec = "highest" if highest else "default"
            with ex.policy_scope(pol), jax.default_matmul_precision(prec):
                out, _ = fn(params, sess._put(np.asarray(prompt)[None]))
            return np.asarray(out[0], np.float64)[:V]

        errs, full_errs, match = [], [], 0
        for req in prompts_by_part[i]:
            full = prefill_logits(sess, req.prompt)[:V]
            check(bool(np.isfinite(full).all()),
                  f"p{i}: non-finite prefill logits")
            served = done_by_uid[req.uid].out[0]
            check(served == int(np.argmax(full)),
                  f"p{i} uid={req.uid}: served first token {served} is not "
                  f"the argmax of its own prefill logits")
            full_errs.append(rel_l2(full, logits(full_ref, sess.params,
                                                 req.prompt, highest=True)))

            got = logits(cut_fn, cut_params, req.prompt)
            want = logits(cut_ref, cut_params, req.prompt, highest=True)
            err = rel_l2(got, want)
            errs.append(err)
            check(err <= REL_L2_BOUND,
                  f"p{i} uid={req.uid}: {ORACLE_LAYERS}-layer prefill logits "
                  f"rel L2 {err:.3g} > {REL_L2_BOUND}")
            first, oracle = int(np.argmax(got)), int(np.argmax(want))
            if first == oracle:
                match += 1
            else:
                # a near-tie may flip under the measured logit error
                gap = want[oracle] - want[first]
                check(gap <= np.max(np.abs(got - want)),
                      f"p{i} uid={req.uid}: first token {first} vs oracle "
                      f"{oracle}, gap {gap:.3g} beyond the logit error")
        log(f"p{i} {runtime.policy_key(i)} vs {ref_pol.spec()}@HIGHEST, "
            f"{ORACLE_LAYERS} layer(s): prefill logits rel L2 max "
            f"{max(errs):.3e} (bound {REL_L2_BOUND}), first tokens equal "
            f"{match}/{len(errs)}; all {cfg.num_layers} layers: rel L2 max "
            f"{max(full_errs):.3e} (not bounded, see ORACLE_LAYERS)")


def phase_one_chip(args, cfg, shape: Shape, compiles: CompileLog):
    import jax
    from repro.kernels import registry
    from repro.models.layers import RuntimeCfg
    from repro.runtime.server import (
        PartitionSpec, ServingRuntime, ServingSpec, TenantSpec)

    params = init_params(cfg, args.seed)
    spec = ServingSpec(
        partitions=tuple(PartitionSpec(policy=p) for p in POLICIES),
        tenants=tuple(TenantSpec(f"tenant{i}", partition=i)
                      for i in range(len(POLICIES))),
        batch_slots=shape.slots, max_len=shape.max_len, paged=True,
        seed=args.seed)
    registry.reset_fallbacks()
    t0 = time.perf_counter()
    runtime = ServingRuntime(params, cfg, spec, rt=RuntimeCfg())
    log(f"ServingRuntime: {spec.n_partitions} partitions "
        f"({', '.join(POLICIES)}), {shape.slots} slots x {shape.max_len} "
        f"paged, built in {time.perf_counter() - t0:.1f} s; {hbm()}")
    tenants = [t.id for t in spec.tenants]

    # warm-up: one request per prompt length per tenant compiles every step
    warm = make_requests(cfg, dataclasses.replace(
        shape, requests=len(shape.prompt_lens) * len(tenants), max_new=2),
        shape.prompt_lens, args.seed + 1, uid0=10_000)
    for j, req in enumerate(warm):
        runtime.submit(tenants[j % len(tenants)], req)
    t0 = time.perf_counter()
    runtime.drain()
    log(f"warm-up (compiles) {time.perf_counter() - t0:.1f} s; {hbm()}")
    log(f"compile s per jitted function: {compiles.summary()}")

    reqs = make_requests(cfg, shape, shape.prompt_lens, args.seed)
    since = time.perf_counter()
    for j, req in enumerate(reqs):
        runtime.submit(tenants[j % len(tenants)], req)
    runtime.drain()
    wall = time.perf_counter() - since
    done = [r for r in reqs if r.done]
    tokens = sum(len(r.out) for r in done)
    log(f"served {len(done)}/{len(reqs)} requests, {tokens} tokens in "
        f"{wall:.2f} s (bring-up reading, not a benchmark); {hbm()}")
    for row in step_walls(runtime, since):
        log(f"  step wall after warm-up (bring-up reading) {row}")
    check(len(done) == len(reqs), "not every request completed")
    check(all(len(r.out) == shape.max_new for r in done),
          "a request stopped short of max_new")

    # the Pallas partition's decode step must hold Pallas kernels
    sess = runtime.sessions[1]
    with sess._policy_scope():
        text = sess.step_fn.lower(
            sess.params, sess.tokens, sess.caches, sess._put(sess.slot_pos),
            sess._page_map, sess.rng).as_text()
    kernels = text.count("tpu_custom_call")
    fallbacks = registry.fallback_count()
    log(f"p1 decode step: {kernels} tpu_custom_call, "
        f"{fallbacks} jnp fallbacks")
    check(fallbacks == 0, f"{fallbacks} pallas entries fell back to jnp")
    if jax.devices()[0].platform == "tpu":
        check(kernels > 0, "Pallas partition's decode step has no "
                           "tpu_custom_call")

    by_uid = {r.uid: r for r in reqs}
    short = [r for r in reqs if len(r.prompt) == min(shape.prompt_lens)]
    prompts_by_part = [
        [r for r in short if r.tenant == tid][:shape.check_prompts]
        for tid in tenants]
    check_against_oracle(runtime, cfg, by_uid, prompts_by_part)


def devices_of(tree):
    import jax
    out = set()
    for leaf in jax.tree_util.tree_leaves(tree):
        out |= set(leaf.devices())
    return out


def serve_chips4(params, cfg, shape: Shape, parts, seed: int):
    """Serve the four-chip workload on ``parts``; returns (runtime, reqs).

    Five tenants: ``load_aware`` puts tenant0..3 on partitions 0..3 and
    tenant4 beside tenant0, and the two of them carry most requests, so
    partition 0 runs hot and the migration loop moves a tenant with
    requests in flight to an idle chip."""
    from repro.models.layers import RuntimeCfg
    from repro.runtime.server import (
        MigrationSpec, PartitionSpec, ServingRuntime, ServingSpec)
    spec = ServingSpec(
        partitions=tuple(PartitionSpec(policy=CHIPS4_POLICY)
                         for _ in parts),
        placement="load_aware", batch_slots=shape.slots,
        max_len=shape.max_len, paged=True, seed=seed,
        migration=MigrationSpec(enabled=True))
    runtime = ServingRuntime(params, cfg, spec, rt=RuntimeCfg(),
                             partitions=parts)
    tenants = [f"tenant{i}" for i in range(5)]
    for tid in tenants:
        runtime.add_tenant(tid)
    reqs = make_requests(cfg, shape, shape.prompt_lens[:1], seed)
    heavy = ("tenant0", "tenant4")
    for j, req in enumerate(reqs):
        if j < 3:
            tid = tenants[1 + j]
            req.max_new = max(2, shape.max_new // 4)
        else:
            tid = heavy[j % 2]
        runtime.submit(tid, req)
    runtime.drain()
    return runtime, reqs


def phase_chips4(args, cfg, shape: Shape, compiles: CompileLog):
    import jax
    from repro.runtime.server import DevicePartition, make_partitions

    devs = jax.devices()
    check(len(devs) >= 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    params = init_params(cfg, args.seed)
    V = cfg.vocab_size

    t0 = time.perf_counter()
    base, base_reqs = serve_chips4(
        params, cfg, shape, [DevicePartition(0, (devs[0],))], args.seed)
    base_logits = {r.uid: prefill_logits(base.sessions[0], r.prompt)[:V]
                   for r in base_reqs}
    base_first = {r.uid: r.out[0] for r in base_reqs}
    check(all(r.done for r in base_reqs), "baseline left requests undone")
    log(f"baseline: 1 partition on {devs[0]}, {len(base_reqs)} requests "
        f"in {time.perf_counter() - t0:.1f} s")
    del base

    t0 = time.perf_counter()
    runtime, reqs = serve_chips4(params, cfg, shape,
                                 make_partitions(4, devs[:4]), args.seed)
    log(f"4 partitions ({CHIPS4_POLICY}, load_aware, migration on): "
        f"{len(reqs)} requests in {time.perf_counter() - t0:.1f} s; "
        f"{hbm()}")
    log(f"compile s per jitted function: {compiles.summary()}")

    for i, sess in enumerate(runtime.sessions):
        dev = runtime.partitions[i].devices[0]
        held = {name: devices_of(tree) for name, tree in (
            ("params", sess.params), ("caches", sess.caches),
            ("page_map", sess._page_map), ("tokens", sess.tokens))}
        check(sess.device == dev and all(d == {dev} for d in held.values()),
              f"p{i}: state not on {dev}: {held}")
        log(f"p{i} on {dev}: params, caches, page map, tokens all there; "
            f"tenants {sorted(runtime.schedulers[i].tenants)}")

    done = [r for r in reqs if r.done]
    check(len(done) == len(reqs), "not every request completed")
    check(all(len(r.out) == r.max_new for r in reqs),
          "a request stopped short of max_new")

    served_by = {}
    for i, tr in enumerate(runtime.tracers):
        for ev in tr.events("prefill"):
            served_by[ev.meta["uid"]] = i
    worst, firsts = 0.0, 0
    for r in reqs:
        i = served_by[r.uid]
        got = prefill_logits(runtime.sessions[i], r.prompt)[:V]
        err = rel_l2(got, base_logits[r.uid])
        worst = max(worst, err)
        check(err <= CHIPS4_REL_L2_BOUND,
              f"uid={r.uid} on p{i}: prefill logits rel L2 {err:.3g} vs the "
              f"one-partition run")
        check(r.out[0] == base_first[r.uid],
              f"uid={r.uid} on p{i}: first token {r.out[0]} vs "
              f"{base_first[r.uid]}")
        firsts += 1
    log(f"prefill logits vs one partition on chip 0: rel L2 max "
        f"{worst:.3e} (bound {CHIPS4_REL_L2_BOUND}); first tokens equal "
        f"{firsts}/{len(reqs)}; prefills per partition "
        f"{collections.Counter(served_by.values())}")

    moves = [m for m in runtime.migrations if m.slots_handed_off
             and runtime.sessions[m.src].device
             != runtime.sessions[m.dst].device]
    for m in runtime.migrations:
        log(f"migration {m.tenant}: p{m.src} -> p{m.dst} at step "
            f"{m.start_step}, {m.slots_handed_off} in-flight slots handed "
            f"off, {m.queued_moved} queued moved, done at {m.done_step}")
    check(bool(moves), "no live migration handed a slot across chips")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config on any platform (CPU rehearsal)")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import setup_compile_cache
    cache = setup_compile_cache()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.reduced:
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"use --reduced to rehearse elsewhere", file=sys.stderr)
        return 1
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"compile cache {cache}")

    from repro.configs import get_arch, get_reduced
    from repro.core import concurrency as cc
    cfg = get_reduced(ARCH) if args.reduced else get_arch(ARCH)
    shape = REDUCED if args.reduced else FULL

    compiles = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    try:
        if args.chips == 4:
            phase_chips4(args, cfg, shape, compiles)
        else:
            phase_one_chip(args, cfg, shape, compiles)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)

    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    log("peak_bytes_in_use: "
        + (f"{peak} ({peak / 1e9:.2f} GB)" if peak else "not reported"))
    log(f"detect_core_count: {cc.detect_core_count()}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
