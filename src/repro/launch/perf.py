"""§Perf hillclimb driver: relower a cell under a named variant, compare
roofline terms against the recorded baseline.

  python -m repro.launch.perf --arch llama3-405b --shape decode_32k \
      --variant decode_2d_tp --out benchmarks/artifacts/perf.jsonl

Variants (hypothesis → change; results in EXPERIMENTS.md §Perf):
  baseline         — recorded dry-run configuration
  fp8              — paper-faithful FP8 matmuls (E4M3 operands, f32 accum):
                     halves matmul operand bytes vs bf16
  fp8_sparse       — FP8 + 2:4 STE pruning (paper's two techniques together)
  decode_2d_tp     — decode activations replicate batch / shard d on "data";
                     matmuls contract against resident 2-D weight shards and
                     psum small activations instead of all-gathering weights
  moe_gather       — gather/scatter MoE dispatch (no one-hot dispatch FLOPs)
  moments_bf16     — bf16 AdamW moments (train-cell HBM fit)
  no_seq_shard     — ablation: disable Megatron-SP activation sharding
"""
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

import argparse
import dataclasses
import json
import time
from typing import Any, Callable, Dict, Optional

import jax

jax.config.update("jax_persistent_cache_min_compile_time_secs", 5.0)

from repro.configs import get_arch, get_shape
from repro.launch import dryrun as dr
from repro.launch import roofline as rl
from repro.launch.mesh import make_production_mesh
from repro.models.layers import RuntimeCfg
from repro.runtime import sharding as sh


@dataclasses.dataclass
class Variant:
    name: str
    cfg_fn: Callable = lambda c: c
    rt_fn: Callable = lambda r: r
    decode_2d_tp: bool = False
    opt_moments_bf16: bool = False


VARIANTS: Dict[str, Variant] = {
    "baseline": Variant("baseline"),
    "fp8": Variant(
        "fp8", cfg_fn=lambda c: dataclasses.replace(c, precision="fp8")),
    "fp8_sparse": Variant(
        "fp8_sparse", cfg_fn=lambda c: dataclasses.replace(
            c, precision="fp8", sparsity_24=True)),
    "decode_2d_tp": Variant("decode_2d_tp", decode_2d_tp=True),
    "moe_gather": Variant(
        "moe_gather",
        rt_fn=lambda r: dataclasses.replace(r, moe_gather_dispatch=True)),
    "moments_bf16": Variant("moments_bf16", opt_moments_bf16=True),
    "no_seq_shard": Variant("no_seq_shard"),
    "grad_bf16": Variant("grad_bf16"),       # bf16 gradient reduction
    "remat_dots": Variant(                   # save dot outputs: fwd weight
        "remat_dots", cfg_fn=lambda c: dataclasses.replace(c, remat="dots")),
    "fsdp_only": Variant("fsdp_only"),       # no TP: batch over both axes
    "fsdp_only_fp8": Variant(                # combo: ZeRO-3 + fp8 weights
        "fsdp_only_fp8",
        cfg_fn=lambda c: dataclasses.replace(c, precision="fp8")),
}


def run_variant(arch_name: str, shape_name: str, variant_name: str,
                with_layer: bool = True,
                backend: Optional[str] = None) -> Dict[str, Any]:
    var = VARIANTS[variant_name]
    cfg = var.cfg_fn(get_arch(arch_name))
    shape = get_shape(shape_name)
    mesh = make_production_mesh()
    seq_shard = variant_name != "no_seq_shard"
    rt = dr.make_rt(cfg, mesh, shape, seq_shard_acts=seq_shard)
    rt = var.rt_fn(rt)
    if backend:
        from repro.core import execution as ex
        rt = dataclasses.replace(rt, policy=ex.ExecutionPolicy(
            precision=cfg.precision,
            sparsity="sparse24" if cfg.sparsity_24 else "dense",
            backend=backend))
    if var.decode_2d_tp:
        rt = dataclasses.replace(rt, shard_fn=sh.make_shard_fn(
            cfg, mesh, shape, decode_2d_tp=True))

    rec: Dict[str, Any] = {"arch": arch_name, "shape": shape_name,
                           "variant": variant_name, "chips": mesh.size,
                           "backend": backend or "jnp"}
    t0 = time.time()
    lower = {"train": dr.lower_train, "prefill": dr.lower_prefill}.get(
        shape.kind, dr.lower_decode)
    if variant_name == "grad_bf16" and shape.kind == "train":
        import functools
        lower = functools.partial(dr.lower_train, grad_compress="bf16")
    if variant_name in ("fsdp_only", "fsdp_only_fp8"):
        import functools
        rt = dataclasses.replace(rt, shard_fn=sh.make_shard_fn(
            cfg, mesh, shape, policy="fsdp_only"))
        lower = functools.partial(lower, policy="fsdp_only")

    import repro.optim.adamw as adamw
    if var.opt_moments_bf16:
        import jax.numpy as jnp
        orig = adamw.AdamWConfig
        adamw.AdamWConfig = lambda **kw: orig(
            moments_dtype=jnp.bfloat16, **kw)
    try:
        compiled, layer = lower(cfg, shape, mesh, rt, with_layer)
        rt_mem = dataclasses.replace(rt, static_loops=False)
        mem_compiled, _ = lower(cfg, shape, mesh, rt_mem, False)
        rec["ok"] = True
        rec["compile_s"] = time.time() - t0
        rec["memory"] = dr._mem_of(mem_compiled)
        full = dr._cost_of(compiled)
        rec["full"] = dataclasses.asdict(full)
        rec["layer"] = dataclasses.asdict(layer) if layer else None
        rec["model_flops"] = rl.model_flops_estimate(cfg, shape)
        rec["min_bytes"] = rl.min_bytes_estimate(cfg, shape)
        roof = rl.assemble(arch_name, shape_name, mesh.size, full, layer,
                           cfg.num_superlayers, rec["model_flops"],
                           min_bytes=rec["min_bytes"], kind=shape.kind)
        rec["roofline"] = roof.to_dict()
        r = rec["roofline"]
        print(f"[{arch_name} × {shape_name} × {variant_name}] "
              f"compute={r['compute_s']:.4f}s memory={r['memory_s']:.4f}s "
              f"coll={r['collective_s']:.4f}s bottleneck={r['bottleneck']} "
              f"frac={r['roofline_fraction']:.4f} "
              f"mem/dev={rec['memory']['per_device_total']/2**30:.1f}GiB")
    except Exception as e:  # noqa: BLE001
        import traceback
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-1500:]
        print(f"[{arch_name} × {shape_name} × {variant_name}] FAIL "
              f"{rec['error'][:160]}")
    finally:
        if var.opt_moments_bf16:
            adamw.AdamWConfig = orig
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--variant", required=True,
                    help=",".join(VARIANTS))
    ap.add_argument("--backend", default=None,
                    choices=[None, "ref", "jnp", "pallas", "pallas_sparse24"],
                    help="route every matmul through this registry backend")
    ap.add_argument("--out", default="benchmarks/artifacts/perf.jsonl")
    args = ap.parse_args()
    for v in args.variant.split(","):
        rec = run_variant(args.arch, args.shape, v, backend=args.backend)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    raise SystemExit(main())
