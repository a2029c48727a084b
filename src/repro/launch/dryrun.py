"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

This is the proof that the distribution config is coherent: for each cell it
jits the real train/prefill/decode step with production shardings over the
16×16 (single-pod) and 2×16×16 (multi-pod) meshes, compiles, and records
``memory_analysis()`` (fits?) + ``cost_analysis()`` + the collective
schedule (roofline terms). It also lowers ONE super-layer standalone so
scan-body costs can be scaled by depth (launch/roofline.py).

Usage:
  python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
  python -m repro.launch.dryrun --all --out benchmarks/artifacts/dryrun.jsonl
"""
# The dry-run (and ONLY the dry-run) needs 512 placeholder devices — set
# before ANY other import; jax locks the device count on first init.
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

# persistent compilation cache (directory: launch/compile_cache.py): sweep
# re-runs and hillclimb iterations skip recompiles of unchanged cells
jax.config.update("jax_persistent_cache_min_compile_time_secs", 5.0)

from repro.configs import (
    ARCH_NAMES, ARCHS, applicable_shapes, get_arch, get_shape)
from repro.configs.base import ArchConfig, ShapeConfig
from repro.launch import roofline as rl
from repro.launch.mesh import make_production_mesh
from repro.models import (
    cache_shape, decode_step, forward, params_shape, prefill)
from repro.models import transformer as tf
from repro.models.layers import RuntimeCfg
from repro.optim import adamw
from repro.runtime import sharding as sh
from repro.runtime import train_loop as tl


# ---------------------------------------------------------------------------
# Runtime config for lowering
# ---------------------------------------------------------------------------

def make_rt(cfg: ArchConfig, mesh, shape: ShapeConfig,
            seq_shard_acts: bool = True) -> RuntimeCfg:
    chunk = 2048 if shape.seq_len >= 32768 else 1024
    chunk_q = chunk
    if cfg.attn_strategy == "seq_tp" and not shape.is_decode:
        # context parallelism: q stays seq-sharded — process all q rows per
        # kv block (slicing a sharded dim would force gathers). Costs the
        # causal-skip FLOPs; documented in EXPERIMENTS.md.
        chunk_q = shape.seq_len
    return RuntimeCfg(
        chunk_q=chunk_q, chunk_kv=chunk,
        static_loops=True,             # exact HLO cost, no hidden scan bodies
        f32_batched_dots=False,        # TPU contract: bf16 operands, f32 acc
        shard_fn=sh.make_shard_fn(cfg, mesh, shape,
                                  seq_shard_acts=seq_shard_acts),
    )


def input_struct(cfg: ArchConfig, shape: ShapeConfig) -> Dict[str, Any]:
    B, S = shape.global_batch, shape.seq_len
    if cfg.input_mode == "embeddings" and not shape.is_decode:
        inputs = jax.ShapeDtypeStruct((B, S, cfg.d_model), jnp.bfloat16)
    else:
        inputs = jax.ShapeDtypeStruct((B, S), jnp.int32)
    if shape.kind == "train":
        return {"inputs": inputs,
                "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    return {"inputs": inputs}


# ---------------------------------------------------------------------------
# Cell lowering
# ---------------------------------------------------------------------------

def _ns(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def _cost_of(compiled) -> rl.CellCost:
    ca = compiled.cost_analysis()
    txt = compiled.as_text()
    return rl.CellCost(
        flops=float(ca.get("flops", 0.0)),
        bytes_accessed=float(ca.get("bytes accessed", 0.0)),
        wire_bytes=rl.collective_wire_bytes(txt),
        collectives=rl.collective_summary(txt),
        wire_bytes_bf16=rl.collective_wire_bytes_bf16(txt),
    )


def _mem_of(compiled) -> Dict[str, float]:
    ma = compiled.memory_analysis()
    per_dev = (ma.argument_size_in_bytes + ma.output_size_in_bytes
               + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    return {
        "argument": ma.argument_size_in_bytes,
        "output": ma.output_size_in_bytes,
        "temp": ma.temp_size_in_bytes,
        "alias": ma.alias_size_in_bytes,
        "per_device_total": per_dev,
    }


def lower_train(cfg: ArchConfig, shape: ShapeConfig, mesh, rt: RuntimeCfg,
                with_layer: bool = True, grad_compress: str = "none",
                policy: str = "tp_fsdp"):
    opt_cfg = adamw.AdamWConfig()
    pshape = params_shape(cfg)
    st_shape = tl.state_shape(cfg, opt_cfg, pshape)
    pspecs = sh.param_specs(cfg, mesh, pshape, policy)
    st_specs = tl.TrainState(
        params=pspecs,
        opt=adamw.AdamWState(step=P(), mu=pspecs, nu=pspecs, master=pspecs),
        grad_error=None)
    bspec = sh.input_spec(cfg, shape, mesh)
    if policy == "fsdp_only":
        ball = ("pod", "data", "model") if "pod" in mesh.axis_names \
            else ("data", "model")
        if shape.global_batch % sh.axis_size(mesh, ball) == 0:
            bspec = P(ball, *tuple(bspec)[1:])
    batch_specs = {"inputs": bspec, "labels": P(bspec[0], None)}
    batch_shape = input_struct(cfg, shape)

    step = tl.make_train_step(cfg, opt_cfg, rt, grad_compress=grad_compress)
    jf = jax.jit(step,
                 in_shardings=(_ns(mesh, st_specs), _ns(mesh, batch_specs)),
                 out_shardings=(_ns(mesh, st_specs), None),
                 donate_argnums=(0,))
    with jax.set_mesh(mesh):
        lowered = jf.lower(st_shape, batch_shape)
        compiled = lowered.compile()

        layer_cost = None
        if with_layer:
            layer_cost = _lower_train_layer(cfg, shape, mesh, rt, pshape,
                                            pspecs, bspec, policy)
    return compiled, layer_cost


def _act_spec(cfg, shape, mesh, bspec, policy="tp_fsdp"):
    """Residual-stream spec matching the act_btd anchor (seq on model)."""
    sx = "model" if shape.seq_len % sh.axis_size(mesh, "model") == 0 else None
    if shape.is_decode or policy == "fsdp_only":
        sx = None
    return P(bspec[0], sx, None)


def _lower_train_layer(cfg, shape, mesh, rt, pshape, pspecs, bspec,
                       policy="tp_fsdp"):
    B, S = shape.global_batch, shape.seq_len
    x = jax.ShapeDtypeStruct((B, S, cfg.d_model), jnp.bfloat16)
    p_super = tf.superlayer_params_slice(pshape)
    ps_specs = sh.param_specs(cfg, mesh, p_super, policy)
    shared = pshape.get("shared_attn")
    sh_specs = sh.param_specs(cfg, mesh, shared, policy) if shared else None
    xspec = _act_spec(cfg, shape, mesh, bspec, policy)

    def fn(x, ct, p_super, shared):
        return tf.superlayer_train_cost(x, ct, p_super, shared, cfg, rt)

    in_sh = (_ns(mesh, xspec), _ns(mesh, xspec), _ns(mesh, ps_specs),
             _ns(mesh, sh_specs) if shared else None)
    out_sh = (_ns(mesh, xspec), _ns(mesh, ps_specs),
              _ns(mesh, sh_specs) if shared else None)
    if shared is None:
        out_sh = out_sh[:2]
    jf = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
    compiled = jf.lower(x, x, p_super, shared).compile()
    return _cost_of(compiled)


def _add_cost(a: rl.CellCost, b: rl.CellCost, mult: float) -> rl.CellCost:
    colls = dict(a.collectives)
    for k, v in b.collectives.items():
        e = colls.setdefault(k, {"count": 0, "wire_bytes": 0.0})
        e["count"] += v["count"] * mult
        e["wire_bytes"] += v["wire_bytes"] * mult
    return rl.CellCost(
        flops=a.flops + mult * b.flops,
        bytes_accessed=a.bytes_accessed + mult * b.bytes_accessed,
        wire_bytes=a.wire_bytes + mult * b.wire_bytes,
        collectives=colls,
        wire_bytes_bf16=a.wire_bytes_bf16 + mult * b.wire_bytes_bf16)


def _lower_ssm_chunk_probe(cfg, shape, mesh, rt, bspec):
    """Per-chunk cost for SSM stacks when the layer probe falls back to
    lax.scan (nchunks > max_static_chunks): cost_analysis counts the chunk
    body once, so the probe lowers ONE chunk standalone and the caller adds
    (nchunks-1) × chunk × blocks_per_superlayer."""
    B = shape.global_batch
    ba = bspec[0]
    Lc = min(rt.ssm_chunk, cfg.ssm_chunk, shape.seq_len)
    if cfg.ssm_kind == "mamba2":
        from repro.models.mamba2 import _ssd_chunk
        nh, hp, N = cfg.ssm_nheads, cfg.ssm_head_dim, cfg.ssm_state
        args = (jax.ShapeDtypeStruct((B, Lc, nh, hp), jnp.float32),
                jax.ShapeDtypeStruct((B, Lc, nh), jnp.float32),
                jax.ShapeDtypeStruct((B, Lc, nh), jnp.float32),
                jax.ShapeDtypeStruct((B, Lc, N), jnp.float32),
                jax.ShapeDtypeStruct((B, Lc, N), jnp.float32),
                jax.ShapeDtypeStruct((B, nh, hp, N), jnp.float32))
        specs = (P(ba, None, "model", None), P(ba, None, None),
                 P(ba, None, None), P(ba, None, None), P(ba, None, None),
                 P(ba, "model", None, None))
        fn = _ssd_chunk
    else:
        from repro.models.rwkv6 import _wkv_chunk
        nh = cfg.d_model // cfg.ssm_head_dim
        hd = cfg.ssm_head_dim
        args = (jax.ShapeDtypeStruct((B, Lc, nh, hd), jnp.float32),) * 4 + (
            jax.ShapeDtypeStruct((nh, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, nh, hd, hd), jnp.float32))
        specs = (P(ba, None, None, None), P(ba, None, None, None),
                 P(ba, None, None, "model"), P(ba, None, None, None),
                 P(None, None), P(ba, None, None, "model"))
        fn = _wkv_chunk
    jf = jax.jit(fn, in_shardings=tuple(_ns(mesh, s) for s in specs))
    compiled = jf.lower(*args).compile()
    nchunks = shape.seq_len // Lc
    return _cost_of(compiled), nchunks


def lower_prefill(cfg: ArchConfig, shape: ShapeConfig, mesh, rt: RuntimeCfg,
                  with_layer: bool = True):
    pshape = params_shape(cfg)
    pspecs = sh.param_specs(cfg, mesh, pshape)
    bspec = sh.input_spec(cfg, shape, mesh)
    batch_shape = input_struct(cfg, shape)["inputs"]

    def fn(params, inputs):
        return prefill(params, inputs, cfg, rt)

    jf = jax.jit(fn, in_shardings=(_ns(mesh, pspecs), _ns(mesh, bspec)),
                 out_shardings=None)
    with jax.set_mesh(mesh):
        lowered = jf.lower(pshape, batch_shape)
        compiled = lowered.compile()

        layer_cost = None
        if with_layer:
            B, S = shape.global_batch, shape.seq_len
            x = jax.ShapeDtypeStruct((B, S, cfg.d_model), jnp.bfloat16)
            p_super = tf.superlayer_params_slice(pshape)
            ps_specs = sh.param_specs(cfg, mesh, p_super)
            shared = pshape.get("shared_attn")
            sh_specs = sh.param_specs(cfg, mesh, shared) if shared else None
            xspec = _act_spec(cfg, shape, mesh, bspec)

            def lfn(x, p_super, shared):
                return tf.superlayer_forward(x, p_super, shared, cfg, rt)
            in_sh = (_ns(mesh, xspec), _ns(mesh, ps_specs),
                     _ns(mesh, sh_specs) if shared else None)
            ljf = jax.jit(lfn, in_shardings=in_sh,
                          out_shardings=(_ns(mesh, xspec), None))
            layer_cost = _cost_of(ljf.lower(x, p_super, shared).compile())
            # SSM chunk scans fall back to lax.scan at this seq len — add
            # the per-chunk correction (body counted once otherwise)
            if cfg.ssm_kind:
                Lc = min(rt.ssm_chunk, cfg.ssm_chunk, shape.seq_len)
                if shape.seq_len // Lc > rt.max_static_chunks:
                    chunk_cost, nchunks = _lower_ssm_chunk_probe(
                        cfg, shape, mesh, rt, bspec)
                    blocks = sum(1 for k in cfg.superlayer_pattern
                                 if k in ("mamba2", "rwkv6"))
                    layer_cost = _add_cost(layer_cost, chunk_cost,
                                           (nchunks - 1) * blocks)
    return compiled, layer_cost


def lower_decode(cfg: ArchConfig, shape: ShapeConfig, mesh, rt: RuntimeCfg,
                 with_layer: bool = True):
    B, S = shape.global_batch, shape.seq_len
    pshape = params_shape(cfg)
    pspecs = sh.param_specs(cfg, mesh, pshape)
    cshape = cache_shape(cfg, B, S)
    cspecs = sh.cache_specs(cfg, shape, mesh, cshape)
    ba = sh.batch_axes(mesh)
    baxes = ba if B % sh.axis_size(mesh, ba) == 0 else None
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)

    def fn(params, tokens, caches, pos):
        return decode_step(params, tokens, caches, pos, cfg, rt)

    jf = jax.jit(
        fn,
        in_shardings=(_ns(mesh, pspecs), _ns(mesh, P(baxes, None)),
                      _ns(mesh, cspecs), _ns(mesh, P())),
        out_shardings=(_ns(mesh, sh.logits_spec(cfg, shape, mesh)),
                       _ns(mesh, cspecs)),
        donate_argnums=(2,))
    with jax.set_mesh(mesh):
        lowered = jf.lower(pshape, tok, cshape, pos)
        compiled = lowered.compile()

        layer_cost = None
        if with_layer:
            x = jax.ShapeDtypeStruct((B, 1, cfg.d_model), jnp.bfloat16)
            p_super = tf.superlayer_params_slice(pshape)
            ps_specs = sh.param_specs(cfg, mesh, p_super)
            c_super = tf.superlayer_cache_slice(cshape)
            cs_specs = jax.tree.map(
                lambda p: P(*tuple(p)[1:]), cspecs["layers"],
                is_leaf=lambda t: isinstance(t, P))
            shared = pshape.get("shared_attn")
            sh_specs = sh.param_specs(cfg, mesh, shared) if shared else None

            def lfn(x, p_super, cache, shared):
                return tf.superlayer_decode(x, p_super, cache, S - 1, shared,
                                            cfg, rt)
            in_sh = (_ns(mesh, P(baxes, None, None)), _ns(mesh, ps_specs),
                     _ns(mesh, cs_specs),
                     _ns(mesh, sh_specs) if shared else None)
            ljf = jax.jit(lfn, in_shardings=in_sh, out_shardings=None)
            layer_cost = _cost_of(ljf.lower(x, p_super, c_super,
                                            shared).compile())
    return compiled, layer_cost


# ---------------------------------------------------------------------------
# One cell end-to-end
# ---------------------------------------------------------------------------

def run_cell(arch_name: str, shape_name: str, multi_pod: bool,
             with_layer: bool = True, verbose: bool = True) -> Dict:
    cfg = get_arch(arch_name)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    rt = make_rt(cfg, mesh, shape)
    rec: Dict[str, Any] = {
        "arch": arch_name, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single", "chips": chips,
    }
    t0 = time.time()
    try:
        lower = {"train": lower_train, "prefill": lower_prefill}.get(
            shape.kind, lower_decode)
        compiled, layer = lower(cfg, shape, mesh, rt, with_layer)
        rec["ok"] = True
        rec["compile_s"] = time.time() - t0
        # XLA:CPU buffer assignment keeps every unrolled block's temps live
        # (scheduling artifact — TPU's memory-aware scheduler serializes), so
        # the authoritative memory probe lowers the scan-based variant of the
        # same step: one block body in HLO => bounded liveness.
        rt_mem = dataclasses.replace(rt, static_loops=False)
        mem_compiled, _ = lower(cfg, shape, mesh, rt_mem, False)
        rec["memory"] = _mem_of(mem_compiled)
        rec["memory_static_sched"] = _mem_of(compiled)
        full = _cost_of(compiled)
        rec["full"] = dataclasses.asdict(full)
        rec["layer"] = dataclasses.asdict(layer) if layer else None
        rec["n_bodies"] = cfg.num_superlayers
        rec["model_flops"] = rl.model_flops_estimate(cfg, shape)
        rec["min_bytes"] = rl.min_bytes_estimate(cfg, shape)
        if not multi_pod:
            roof = rl.assemble(arch_name, shape_name, chips, full, layer,
                               cfg.num_superlayers, rec["model_flops"],
                               min_bytes=rec["min_bytes"], kind=shape.kind)
            rec["roofline"] = roof.to_dict()
        if verbose:
            print(f"[{arch_name} × {shape_name} × {rec['mesh']}] OK "
                  f"compile={rec['compile_s']:.1f}s "
                  f"mem/dev={rec['memory']['per_device_total']/2**30:.2f}GiB")
            print("  memory_analysis:", rec["memory"])
            print("  cost_analysis: flops=%.3e bytes=%.3e wire=%.3e"
                  % (full.flops, full.bytes_accessed, full.wire_bytes))
            if "roofline" in rec:
                r = rec["roofline"]
                print("  roofline: compute=%.4fs memory=%.4fs coll=%.4fs "
                      "bottleneck=%s frac=%.3f"
                      % (r["compute_s"], r["memory_s"], r["collective_s"],
                         r["bottleneck"], r["roofline_fraction"]))
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec["ok"] = False
        rec["compile_s"] = time.time() - t0
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        if verbose:
            print(f"[{arch_name} × {shape_name} × {rec['mesh']}] FAIL "
                  f"{rec['error'][:200]}")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-layer", action="store_true")
    ap.add_argument("--out", default=None, help="append JSONL records here")
    ap.add_argument("--skip-done", action="store_true")
    args = ap.parse_args()

    done = set()
    if args.out and args.skip_done and os.path.exists(args.out):
        for line in open(args.out):
            try:
                r = json.loads(line)
                if r.get("ok"):
                    done.add((r["arch"], r["shape"], r["mesh"]))
            except json.JSONDecodeError:
                pass

    cells = []
    if args.all:
        for name in ARCH_NAMES:
            for shp in applicable_shapes(ARCHS[name]):
                cells.append((name, shp.name, False))
                cells.append((name, shp.name, True))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape, args.multi_pod)]

    n_ok = 0
    for arch, shp, multi in cells:
        key = (arch, shp, "multi" if multi else "single")
        if key in done:
            print(f"[{arch} × {shp} × {key[2]}] cached, skipping")
            n_ok += 1
            continue
        rec = run_cell(arch, shp, multi,
                       with_layer=(not args.no_layer) and not multi)
        n_ok += bool(rec["ok"])
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
    print(f"dry-run: {n_ok}/{len(cells)} cells OK")
    return 0 if n_ok == len(cells) else 1


if __name__ == "__main__":
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    raise SystemExit(main())
