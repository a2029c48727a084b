"""Jit'd public wrappers around the Pallas kernels.

``interpret`` follows :func:`repro.kernels.registry.interpret_mode`: on
the CPU the kernel body executes through the Pallas interpreter — bit-accurate control flow,
same BlockSpec tiling — while on TPU the same call lowers through Mosaic.
Model code calls these via ``RuntimeCfg.use_pallas``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import fp8 as fp8lib
from repro.kernels import flash_attention as fa
from repro.kernels import fp8_matmul as fm
from repro.kernels import sparse24_matmul as sm
from repro.kernels.registry import interpret_mode as _interpret


def fp8_matmul(x_q: jax.Array, w_q: jax.Array, x_inv_scale=1.0,
               w_inv_scale=1.0, out_dtype=jnp.bfloat16, **blocks) -> jax.Array:
    """Pre-quantized fp8 GEMM with scalar descale."""
    acc = fm.fp8_matmul_pallas(x_q, w_q, interpret=_interpret(), **blocks)
    return (acc * (x_inv_scale * w_inv_scale)).astype(out_dtype)


def fp8_matmul_dynamic(x: jax.Array, w: jax.Array,
                       out_dtype=jnp.bfloat16, **blocks) -> jax.Array:
    """Dynamic per-tensor scaling + Pallas fp8 GEMM. x: (..., K); w: (K, N)."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    fmax = fp8lib.E4M3_MAX
    xa = jnp.maximum(jnp.max(jnp.abs(x2.astype(jnp.float32))), 1e-12)
    wa = jnp.maximum(jnp.max(jnp.abs(w.astype(jnp.float32))), 1e-12)
    xs, ws = fmax / xa, fmax / wa
    x_q = (x2.astype(jnp.float32) * xs).astype(fp8lib.E4M3)
    w_q = (w.astype(jnp.float32) * ws).astype(fp8lib.E4M3)
    out = fp8_matmul(x_q, w_q, 1.0 / xs, 1.0 / ws, out_dtype=out_dtype,
                     **blocks)
    return out.reshape(*lead, w.shape[-1])


def sparse24_matmul(x: jax.Array, values: jax.Array, meta: jax.Array,
                    out_dtype=jnp.bfloat16, **blocks) -> jax.Array:
    """Packed 2:4 GEMM. x: (..., K); values (K/2, N); meta (K/8, N)."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    out = sm.sparse24_matmul_pallas(x2, values, meta,
                                    interpret=_interpret(),
                                    out_dtype=out_dtype, **blocks)
    return out.reshape(*lead, values.shape[-1])


def block24_matmul(x: jax.Array, w_packed: jax.Array, kept_idx,
                   block: int = 128, out_dtype=jnp.bfloat16) -> jax.Array:
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    out = sm.block24_matmul_pallas(x2, w_packed, tuple(kept_idx), block=block,
                                   out_dtype=out_dtype,
                                   interpret=_interpret())
    return out.reshape(*lead, w_packed.shape[-1])


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, **blocks) -> jax.Array:
    """q: (B, S, h, hd) (model layout); k/v: (B, S, kvh, hd)."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = fa.flash_attention_pallas(qt, kt, vt, causal=causal,
                                    interpret=_interpret(), **blocks)
    return out.transpose(0, 2, 1, 3)
