"""Matmul backend registry — the one seam every GEMM in the system crosses.

The paper's central finding is that FP8, concurrency, and 2:4 sparsity pay
off only *context-dependently* (occupancy §5, fairness §6, break-even §7).
Instead of hard-wiring each technique at call sites, every matmul consumer
routes through a named :class:`MatmulBackend`, selected by an
``ExecutionPolicy`` (core/execution.py). Each backend exposes four entry
points with identical signatures:

  ``dense(x, w)``                   — bf16/f32 GEMM, f32 accumulation
  ``fp8(x, w)``                     — dynamic per-tensor-scaled FP8 GEMM
  ``fp8_qdot(x_q, w_q, xs, ws)``    — pre-quantized FP8 GEMM + descale
                                      (the delayed-scaling training hook)
  ``sparse24(x, values, meta)``     — packed 2:4 GEMM

Registered backends:

  ``ref``             pure-f32 oracles (numerics ground truth)
  ``jnp``             XLA ``dot_general`` paths (CPU/TPU default)
  ``pallas``          Pallas TPU kernels; on CPU the same BlockSpec tiling
                      executes through the interpreter (``interpret=True``).
                      M is zero-padded to a multiple of 8, so any row count
                      runs the kernel; a K/N that cannot tile falls back to
                      ``jnp`` with a warning and a count
                      (:func:`fallback_count`)
  ``pallas_sparse24`` Pallas with the packed-2:4 kernel as the *primary*
                      path: its ``dense`` entry prunes + packs the weight
                      on the fly (serving-style, no STE)

``x`` may carry leading batch dims; they are flattened into M. ``bm/bn/bk``
override the block shapes (``None`` → kernel defaults / autotune cache).
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import math
import warnings
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import concurrency as cc
from repro.core import fp8 as fp8lib
from repro.core import sparsity as sp
from repro.kernels import fp8_matmul as fm
from repro.kernels import sparse24_matmul as sm

# The four matmul flavors every backend provides — also the valid ``kind``
# values for the async :meth:`MatmulBackend.dispatch` entry point.
KINDS = ("dense", "fp8", "fp8_qdot", "sparse24")


@dataclasses.dataclass(frozen=True)
class MatmulBackend:
    """One named execution substrate for the four matmul flavors."""
    name: str
    dense: Callable
    fp8: Callable
    fp8_qdot: Callable
    sparse24: Callable
    description: str = ""

    def entry(self, kind: str) -> Callable:
        if kind not in KINDS:
            raise KeyError(
                f"unknown matmul kind {kind!r}; one of {', '.join(KINDS)}")
        return getattr(self, kind)

    def dispatch(self, kind: str, *operands, lane=None, overlap_group=-1,
                 **kw) -> "cc.LaneHandle":
        """Async entry point: enqueue ``kind`` through JAX's dispatch queue
        and return a joinable :class:`~repro.core.concurrency.LaneHandle`
        (``join()`` → ``jax.block_until_ready`` on the result). Available
        on every backend — off-TPU the pallas entries already run through
        the interpret fallback, so dispatch-and-join works on CPU CI too.

        ``lane`` threads the call onto a caller-owned
        :class:`~repro.core.concurrency.ExecutionLane` (so its tracer and
        bookkeeping see the op); without one, a throwaway lane named after
        the backend is used."""
        fn = self.entry(kind)
        if lane is None:
            lane = cc.ExecutionLane(f"{self.name}:{kind}")
        return lane.dispatch(functools.partial(fn, *operands, **kw),
                             label=f"{self.name}.{kind}",
                             overlap_group=overlap_group)


_REGISTRY: Dict[str, MatmulBackend] = {}


def register_backend(backend: MatmulBackend) -> MatmulBackend:
    _REGISTRY[backend.name] = backend
    return backend


def get_backend(name: str) -> MatmulBackend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown matmul backend {name!r}; available: "
            f"{', '.join(sorted(_REGISTRY))}") from None


def available_backends() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def interpret_mode() -> bool:
    """Run Pallas kernels through the interpreter: on the CPU only."""
    return jax.default_backend() == "cpu"


# (entry, M, K, N) -> trace-time count of pallas entries that ran the jnp
# path because a K/N block could not tile.
_FALLBACKS: "collections.Counter[Tuple[str, int, int, int]]" = \
    collections.Counter()


def _fallback(entry: str, m: int, k: int, n: int) -> None:
    key = (entry, m, k, n)
    if key not in _FALLBACKS:
        warnings.warn(
            f"pallas {entry} {m}x{k}x{n}: K/N blocks cannot tile; running "
            f"the jnp path instead", RuntimeWarning, stacklevel=3)
    _FALLBACKS[key] += 1


def fallback_count() -> int:
    """Pallas entries traced onto the jnp path since the last reset."""
    return sum(_FALLBACKS.values())


def reset_fallbacks() -> None:
    _FALLBACKS.clear()


# ---------------------------------------------------------------------------
# Shape plumbing
# ---------------------------------------------------------------------------

def _flatten_lead(x: jax.Array) -> Tuple[jax.Array, Tuple[int, ...]]:
    lead = x.shape[:-1]
    return x.reshape(-1, x.shape[-1]), lead


def _fit(dim: int, pref: Optional[int], default: int) -> int:
    """Largest block <= pref(/default) that divides ``dim``."""
    b = min(pref or default, dim)
    if dim % b:
        b = math.gcd(dim, b)
    return max(b, 1)


def _tileable(*blocks: int) -> bool:
    """Reject sub-MXU-lane tiles — interpret grids explode and Mosaic won't
    lower them; the caller falls back to the jnp path instead."""
    return all(b % 8 == 0 for b in blocks)


def _round8(m: int) -> int:
    return -(-m // 8) * 8


def _pad_rows(x2: jax.Array, mp: int) -> jax.Array:
    """Zero-pad (M, K) to (mp, K): decode M is the slot count, often not
    a multiple of the kernel's 8-row tile. Zero rows add nothing."""
    m = x2.shape[0]
    return x2 if mp == m else jnp.pad(x2, ((0, mp - m), (0, 0)))


# ---------------------------------------------------------------------------
# ref — exact-f32 oracles
# ---------------------------------------------------------------------------

def _f32_dot(a: jax.Array, b: jax.Array) -> jax.Array:
    return jax.lax.dot_general(
        a.astype(jnp.float32), b.astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)


def _ref_dense(x, w, *, out_dtype=jnp.bfloat16, bm=None, bn=None, bk=None):
    x2, lead = _flatten_lead(x)
    return _f32_dot(x2, w).astype(out_dtype).reshape(*lead, w.shape[-1])


def _ref_fp8(x, w, *, out_dtype=jnp.bfloat16, bm=None, bn=None, bk=None):
    x2, lead = _flatten_lead(x)
    xq, xinv = fp8lib.quantize_weight_static(x2)
    wq, winv = fp8lib.quantize_weight_static(w)
    out = _f32_dot(xq, wq) * (xinv * winv)
    return out.astype(out_dtype).reshape(*lead, w.shape[-1])


def _ref_fp8_qdot(x_q, w_q, x_inv_scale=1.0, w_inv_scale=1.0, *,
                  out_dtype=jnp.float32, bm=None, bn=None, bk=None):
    x2, lead = _flatten_lead(x_q)
    out = _f32_dot(x2, w_q) * (x_inv_scale * w_inv_scale)
    return out.astype(out_dtype).reshape(*lead, w_q.shape[-1])


def _ref_sparse24(x, values, meta, *, out_dtype=jnp.bfloat16,
                  bm=None, bn=None, bk=None):
    return sp.sparse24_matmul_ref(x, values, meta, out_dtype=out_dtype)


register_backend(MatmulBackend(
    name="ref",
    dense=_ref_dense,
    fp8=_ref_fp8,
    fp8_qdot=_ref_fp8_qdot,
    sparse24=_ref_sparse24,
    description="pure-f32 jnp oracles (ground truth for allclose tests)",
))


# ---------------------------------------------------------------------------
# jnp — XLA dot_general (native operand dtypes, f32 accumulation)
# ---------------------------------------------------------------------------

def _jnp_dense(x, w, *, out_dtype=jnp.bfloat16, bm=None, bn=None, bk=None):
    acc = jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return acc.astype(out_dtype)


def _jnp_fp8(x, w, *, out_dtype=jnp.bfloat16, bm=None, bn=None, bk=None):
    return fp8lib.dynamic_fp8_matmul(x, w, out_dtype=out_dtype)


def _jnp_fp8_qdot(x_q, w_q, x_inv_scale=1.0, w_inv_scale=1.0, *,
                  out_dtype=jnp.float32, bm=None, bn=None, bk=None):
    return fp8lib.fp8_dot(x_q, w_q, x_inv_scale, w_inv_scale,
                          out_dtype=out_dtype)


register_backend(MatmulBackend(
    name="jnp",
    dense=_jnp_dense,
    fp8=_jnp_fp8,
    fp8_qdot=_jnp_fp8_qdot,
    sparse24=_ref_sparse24,
    description="XLA dot_general paths (the CPU/TPU non-kernel default)",
))


# ---------------------------------------------------------------------------
# pallas — blocked TPU kernels (interpreter on CPU), jnp shape fallback.
#
# ``pallas_call`` has no AD rule, so each entry is wrapped in a custom_vjp:
# the Pallas kernel computes the forward product, and the backward pass
# differentiates the numerically-equivalent jnp reference. That keeps
# ``--backend pallas`` usable under jax.grad (training) with gradients
# identical to the jnp backend's.
# ---------------------------------------------------------------------------

def _pallas_blocks(M: int, K: int, N: int, bm, bn, bk,
                   dbm: int, dbn: int, dbk: int) -> Tuple[int, int, int]:
    return (_fit(M, bm, dbm), _fit(N, bn, dbn), _fit(K, bk, dbk))


def _fwd_with_ref_grad(pallas_fn: Callable, ref_fn: Callable, *operands):
    """Run ``pallas_fn`` forward; differentiate through ``ref_fn``."""

    @jax.custom_vjp
    def f(*args):
        return pallas_fn(*args)

    def fwd(*args):
        return pallas_fn(*args), args

    def bwd(res, g):
        _, vjp = jax.vjp(ref_fn, *res)
        return vjp(g)

    f.defvjp(fwd, bwd)
    return f(*operands)


def _pallas_dense(x, w, *, out_dtype=jnp.bfloat16, bm=None, bn=None, bk=None):
    x2, lead = _flatten_lead(x)
    (M, K), N = x2.shape, w.shape[-1]
    Mp = _round8(M)
    fbm, fbn, fbk = _pallas_blocks(Mp, K, N, bm, bn, bk,
                                   fm.DEFAULT_BM, fm.DEFAULT_BN, fm.DEFAULT_BK)
    if not _tileable(fbm, fbn, fbk):
        _fallback("dense", M, K, N)
        return _jnp_dense(x, w, out_dtype=out_dtype)

    def kernel(x2, w):
        acc = fm.fp8_matmul_pallas(_pad_rows(x2, Mp), w,
                                   bm=fbm, bn=fbn, bk=fbk,
                                   interpret=interpret_mode())
        return acc[:M].astype(out_dtype)

    out = _fwd_with_ref_grad(
        kernel, lambda a, b: _jnp_dense(a, b, out_dtype=out_dtype), x2, w)
    return out.reshape(*lead, N)


def _pallas_fp8(x, w, *, out_dtype=jnp.bfloat16, bm=None, bn=None, bk=None):
    x2, lead = _flatten_lead(x)
    (M, K), N = x2.shape, w.shape[-1]
    Mp = _round8(M)
    fbm, fbn, fbk = _pallas_blocks(Mp, K, N, bm, bn, bk,
                                   fm.DEFAULT_BM, fm.DEFAULT_BN, fm.DEFAULT_BK)
    if not _tileable(fbm, fbn, fbk):
        _fallback("fp8", M, K, N)
        return _jnp_fp8(x, w, out_dtype=out_dtype)

    def kernel(x2, w):
        xq, xinv = fp8lib.quantize_weight_static(x2)
        wq, winv = fp8lib.quantize_weight_static(w)
        acc = fm.fp8_matmul_pallas(_pad_rows(xq, Mp), wq,
                                   bm=fbm, bn=fbn, bk=fbk,
                                   interpret=interpret_mode())
        return (acc[:M] * (xinv * winv)).astype(out_dtype)

    out = _fwd_with_ref_grad(
        kernel, lambda a, b: _jnp_fp8(a, b, out_dtype=out_dtype), x2, w)
    return out.reshape(*lead, N)


def _pallas_fp8_qdot(x_q, w_q, x_inv_scale=1.0, w_inv_scale=1.0, *,
                     out_dtype=jnp.float32, bm=None, bn=None, bk=None):
    x2, lead = _flatten_lead(x_q)
    (M, K), N = x2.shape, w_q.shape[-1]
    Mp = _round8(M)
    fbm, fbn, fbk = _pallas_blocks(Mp, K, N, bm, bn, bk,
                                   fm.DEFAULT_BM, fm.DEFAULT_BN, fm.DEFAULT_BK)
    if not _tileable(fbm, fbn, fbk):
        _fallback("fp8_qdot", M, K, N)
        return _jnp_fp8_qdot(x_q, w_q, x_inv_scale, w_inv_scale,
                             out_dtype=out_dtype)
    acc = fm.fp8_matmul_pallas(_pad_rows(x2, Mp), w_q,
                               bm=fbm, bn=fbn, bk=fbk,
                               interpret=interpret_mode())[:M]
    return (acc * (x_inv_scale * w_inv_scale)) \
        .astype(out_dtype).reshape(*lead, N)


def _pallas_sparse24(x, values, meta, *, out_dtype=jnp.bfloat16,
                     bm=None, bn=None, bk=None):
    x2, lead = _flatten_lead(x)
    (M, K), N = x2.shape, values.shape[-1]
    Mp = _round8(M)
    fbm, fbn, fbk = _pallas_blocks(Mp, K, N, bm, bn, bk,
                                   sm.DEFAULT_BM, sm.DEFAULT_BN, sm.DEFAULT_BK)
    lanes_ok = fbn % sm.LANES == 0 or fbn == N < sm.LANES
    if not _tileable(fbm, fbn, fbk) or fbk % 64 or not lanes_ok:
        _fallback("sparse24", M, K, N)
        return _ref_sparse24(x, values, meta, out_dtype=out_dtype)

    def kernel(x2, values, meta):
        return sm.sparse24_matmul_pallas(_pad_rows(x2, Mp), values, meta,
                                         bm=fbm, bn=fbn, bk=fbk,
                                         out_dtype=out_dtype,
                                         interpret=interpret_mode())[:M]

    out = _fwd_with_ref_grad(
        kernel,
        lambda a, v, m: _ref_sparse24(a, v, m, out_dtype=out_dtype),
        x2, values, meta)
    return out.reshape(*lead, N)


register_backend(MatmulBackend(
    name="pallas",
    dense=_pallas_dense,
    fp8=_pallas_fp8,
    fp8_qdot=_pallas_fp8_qdot,
    sparse24=_pallas_sparse24,
    description="blocked Pallas TPU kernels (interpret fallback on CPU)",
))


# ---------------------------------------------------------------------------
# pallas_sparse24 — packed-2:4 as the primary path: dense weights are
# pruned + packed inside the traced computation (serving-style, no STE), so
# a single policy switch measures the paper's §7 bandwidth trade on any
# workload. NOTE: the prune+pack re-executes per call — right for one-shot
# backend sweeps; steady-state serving should pre-pack once via
# ``execution.pack_weight`` and hand ``PackedWeight``s to the model, which
# routes straight to the packed kernel.
# ---------------------------------------------------------------------------

def _sparse24_primary_dense(x, w, *, out_dtype=jnp.bfloat16,
                            bm=None, bn=None, bk=None):
    if w.ndim != 2 or w.shape[0] % 8:
        return _pallas_dense(x, w, out_dtype=out_dtype, bm=bm, bn=bn, bk=bk)
    values, meta = sp.pack_24(sp.prune_24(w))
    return _pallas_sparse24(x, values, meta, out_dtype=out_dtype,
                            bm=bm, bn=bn, bk=bk)


register_backend(MatmulBackend(
    name="pallas_sparse24",
    dense=_sparse24_primary_dense,
    fp8=_pallas_fp8,
    fp8_qdot=_pallas_fp8_qdot,
    sparse24=_pallas_sparse24,
    description="Pallas with on-the-fly 2:4 prune+pack for dense weights",
))
