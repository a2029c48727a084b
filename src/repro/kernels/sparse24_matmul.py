"""Pallas TPU kernel: packed 2:4-sparse GEMM (paper §7, TPU-native form).

MI300A's sparse MFMA skips the pruned half of the FLOPs. TPU has no sparse
MXU, so the win is re-derived from the memory hierarchy (DESIGN.md §2): the
weight streams from HBM in *packed* form — values (K/2, N) + 2-bit metadata
(K/8, N) ≈ 0.3125× the bytes of a dense bf16 weight — and is decompressed
**in VMEM by the VPU** while the MXU consumes the previous block (the grid
pipeline double-buffers). FLOPs are unchanged; HBM weight traffic halves+.
That converts directly to speedup exactly where LLM serving is
weight-bandwidth-bound (decode) — the TPU version of the paper's
"context-dependent sparsity benefit".

Decompression per block (pure VPU ops, no gather). Packed row ``4q + j``
holds slot ``j % 2`` of group ``2q + j // 2``, and field ``j`` of meta row
``q`` is its in-group position. For each 128-lane chunk the values are
widened to f32 in a scratch buffer, so rows ``4q + j`` come out of one
strided load per ``j``; a compare-and-select per position ``p`` gives the
dense rows ``8q + c`` (``c = 4 * (j // 2) + p``) as eight ``(bk/8, 128)``
slabs. The slabs are stored c-major within every 64-row chunk, and ``x``'s
columns are permuted the same way outside the kernel, so the MXU contracts
the decompressed block without any sublane shuffle in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BM = 128
DEFAULT_BN = 256
DEFAULT_BK = 256          # K-block of the *dense* K dimension
LANES = 128


def permute_k(x: jax.Array) -> jax.Array:
    """Column order the kernel's decompressed block uses: within every 64
    columns, ``x[:, 64u + 8q + c]`` moves to ``64u + 8c + q``."""
    m, k = x.shape
    return x.reshape(m, k // 64, 8, 8).swapaxes(2, 3).reshape(m, k)


def _sparse24_kernel(x_ref, v_ref, m_ref, o_ref, acc_ref, w_ref, vs_ref, *,
                     k_steps: int, bk: int, bn: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    rows = bk // 8
    cw = min(LANES, bn)
    for n0 in range(0, bn, cw):                                   # VPU
        lanes = slice(n0, n0 + cw)
        vs_ref[...] = v_ref[:, lanes].astype(jnp.float32)
        meta = m_ref[:, lanes].astype(jnp.int32)   # Mosaic shifts need i32
        for h in range(2):
            ja, jb = 2 * h, 2 * h + 1
            va = vs_ref[pl.ds(ja, rows, stride=4), :]
            vb = vs_ref[pl.ds(jb, rows, stride=4), :]
            ia = (meta >> (2 * ja)) & 3
            ib = (meta >> (2 * jb)) & 3
            for p in range(4):
                c = 4 * h + p
                slab = jnp.where(ia == p, va, 0.0) + jnp.where(ib == p, vb, 0.0)
                w_ref[:, 8 * c:8 * c + 8, lanes] = slab.reshape(
                    bk // 64, 8, cw)
    x = x_ref[...]
    w = w_ref[...].reshape(bk, bn).astype(x.dtype)  # exact: bf16/fp8 values
    acc_ref[...] += jax.lax.dot_general(                          # MXU
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "out_dtype",
                                             "interpret"))
def sparse24_matmul_pallas(x: jax.Array, values: jax.Array, meta: jax.Array,
                           *, bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                           bk: int = DEFAULT_BK, out_dtype=jnp.bfloat16,
                           interpret: bool = False) -> jax.Array:
    """x: (M, K); values: (K/2, N); meta: (K/8, N) uint8 → (M, N).

    Needs ``bk % 64 == 0``, and ``bn % 128 == 0`` or ``bn < 128``."""
    M, K = x.shape
    K2, N = values.shape
    assert K == 2 * K2, (x.shape, values.shape)
    assert meta.shape == (K // 8, N), meta.shape
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0
    assert bk % 64 == 0 and (bn % LANES == 0 or bn < LANES), (bk, bn)
    k_steps = K // bk
    if x.dtype != jnp.bfloat16:
        x = x.astype(jnp.float32)

    return pl.pallas_call(
        functools.partial(_sparse24_kernel, k_steps=k_steps, bk=bk, bn=bn),
        grid=(M // bm, N // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk // 2, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bk // 8, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((bk // 64, 64, bn), jnp.float32),
                        pltpu.VMEM((bk // 2, min(LANES, bn)), jnp.float32)],
        interpret=interpret,
    )(permute_k(x), values, meta)


# ---------------------------------------------------------------------------
# Beyond-paper: block-2:4 tile-skipping kernel — real FLOP reduction.
# The kept K-block indices are static (weights are pruned offline), so the
# grid simply iterates the kept half of K; BlockSpec index_map uses a
# compile-time lookup table.
# ---------------------------------------------------------------------------

def block24_matmul_pallas(x: jax.Array, w_packed: jax.Array,
                          kept_idx: tuple, *, block: int = 128,
                          bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                          out_dtype=jnp.bfloat16,
                          interpret: bool = False) -> jax.Array:
    """x: (M, K_dense); w_packed: (K_dense/2, N) — kept K-blocks concatenated.

    ``kept_idx``: static tuple of kept dense-K block indices (len = K/2/block).
    FLOPs: M·N·K/2 — an actual 2× reduction vs dense, unlike element 2:4.
    """
    M, K = x.shape
    Kh, N = w_packed.shape
    assert Kh == K // 2
    assert len(kept_idx) == Kh // block
    bm, bn = min(bm, M), min(bn, N)
    assert M % bm == 0 and N % bn == 0 and Kh % block == 0
    k_steps = Kh // block
    kept = tuple(int(i) for i in kept_idx)

    def kernel(x_ref, w_ref, o_ref, acc_ref):
        @pl.when(pl.program_id(2) == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        @pl.when(pl.program_id(2) == k_steps - 1)
        def _store():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)

    def x_index(i, j, k):
        # jump to the kept dense-K block (static switch over k)
        kd = jax.lax.switch(k, [lambda v=v: jnp.int32(v) for v in kept])
        return (i, kd)

    return pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, block), x_index),
            pl.BlockSpec((block, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, w_packed)
