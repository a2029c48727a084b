"""Pallas TPU kernel: blocked online-softmax (flash) attention.

Used by the transformer-style case study (paper §8.1) as the fused
"increase occupancy via fusion" option the paper recommends (§9.2): QK^T,
softmax and PV stay in VMEM across the KV sweep, so the only HBM traffic is
Q/K/V/O — attention becomes grid-parallel enough to fill cores even at
modest batch (the occupancy lever the paper measures in Fig 2).

Layout: q (B, h, Sq, hd); k/v (B, kvh, Skv, hd) — GQA resolved by the
BlockSpec index map (query head h reads kv head h // group).

grid = (B, h, Sq/bq, Skv/bk), kv innermost; m/l/acc live in VMEM scratch
across the kv sweep. m and l are kept 2-D, ``(bq, 128)`` with the value
repeated across lanes, because Mosaic cannot lay out a 1-D row statistic
broadcast back over the block. Causal blocks above the diagonal are masked; fully
masked blocks are skipped via ``pl.when`` (no MXU pass issued).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

DEFAULT_BQ = 512
DEFAULT_BK = 512
LANES = 128


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  k_steps: int, bq: int, bk: int, scale: float, causal: bool):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal: skip blocks entirely above the diagonal
    run = (j * bk <= i * bq + bq - 1) if causal else (j >= 0)

    @pl.when(run)
    def _block():
        q = q_ref[0, 0].astype(jnp.float32)                 # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)                 # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qi = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            ki = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qi >= ki, s, NEG_INF)
        m_prev = m_ref[...]                                  # (bq, LANES)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        m_col = m_new[:, :1]                                 # (bq, 1)
        p = jnp.exp(s - m_col)
        p = jnp.where(m_col > NEG_INF / 2, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = (acc_ref[...] * alpha[:, :1]
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(j == k_steps - 1)
    def _store():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk",
                                             "interpret"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, bq: int = DEFAULT_BQ,
                           bk: int = DEFAULT_BK,
                           interpret: bool = False) -> jax.Array:
    """q: (B, h, Sq, hd); k/v: (B, kvh, Skv, hd) → (B, h, Sq, hd)."""
    B, h, sq, hd = q.shape
    _, kvh, skv, _ = k.shape
    assert h % kvh == 0
    group = h // kvh
    bq, bk = min(bq, sq), min(bk, skv)
    assert sq % bq == 0 and skv % bk == 0
    k_steps = skv // bk
    scale = 1.0 / math.sqrt(hd)

    kernel = functools.partial(_flash_kernel, k_steps=k_steps, bq=bq, bk=bk,
                               scale=scale, causal=causal)
    return pl.pallas_call(
        kernel,
        grid=(B, h, sq // bq, k_steps),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, hh, i, j: (b, hh, i, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, hh, i, j, g=group: (b, hh // g, j, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, hh, i, j, g=group: (b, hh // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, hh, i, j: (b, hh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, h, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),  # m
            pltpu.VMEM((bq, LANES), jnp.float32),  # l
            pltpu.VMEM((bq, hd), jnp.float32),     # acc
        ],
        interpret=interpret,
    )(q, k, v)
