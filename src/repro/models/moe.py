"""Mixture-of-Experts layer: top-k router + grouped capacity dispatch.

GShard/Switch-style einsum dispatch (pjit-friendly — experts shard on the
``model`` mesh axis when E divides it, per-expert ``d_ff`` shards otherwise;
see runtime/sharding.py). Tokens are processed in groups of
``cfg.moe_group_size`` so the dispatch one-hot stays O(T · gs · k · cf)
rather than O(T²k/E).

The router runs in f32 (paper §9.2 mixed-precision guidance: keep
precision-sensitive ops high while expert GEMMs run FP8/2:4).
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.layers import (
    RuntimeCfg, DEFAULT_RT, batched_einsum, dense, shard_tag, swiglu_mlp,
    _init)


def capacity(cfg: ArchConfig, group_size: int) -> int:
    c = int(math.ceil(group_size * cfg.experts_top_k
                      * cfg.moe_capacity_factor / cfg.num_experts))
    return max(c, 1)


def router_dispatch(logits: jax.Array, cfg: ArchConfig,
                    cap: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k routing with capacity.

    logits: (G, gs, E) f32. Returns
      combine  (G, gs, E, C) f32 — softmax weight where routed, else 0,
      dispatch (G, gs, E, C) bool,
      aux      scalar load-balance loss (Switch aux).
    """
    G, gs, E = logits.shape
    k = cfg.experts_top_k
    gates = jax.nn.softmax(logits, axis=-1)                     # (G, gs, E)

    # top-k expert ids per token
    topv, topi = jax.lax.top_k(gates, k)                        # (G, gs, k)
    # normalize selected gate values (standard for k>1)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

    # one-hot per choice: (G, gs, k, E)
    onehot = jax.nn.one_hot(topi, E, dtype=jnp.float32)

    # position in expert: priority = (choice-major, token-minor) — earlier
    # choices win capacity slots first (GShard convention).
    # flatten (k, gs) -> priority order, cumsum per expert.
    oh_kt = onehot.transpose(0, 2, 1, 3).reshape(G, k * gs, E)  # choice-major
    pos_flat = jnp.cumsum(oh_kt, axis=1) - oh_kt                # pos within expert
    pos = pos_flat.reshape(G, k, gs, E).transpose(0, 2, 1, 3)   # (G, gs, k, E)
    in_cap = (pos < cap) & (onehot > 0)

    # scatter into capacity slots: (G, gs, E, C)
    slot = jax.nn.one_hot(pos.astype(jnp.int32), cap, dtype=jnp.float32)
    slot = slot * in_cap[..., None]                             # (G, gs, k, E, C)
    dispatch = slot.sum(axis=2) > 0                             # (G, gs, E, C)
    combine = (slot * topv[..., None, None] * onehot[..., None]).sum(axis=2)

    # Switch load-balance aux: E * mean(fraction routed)·mean(gate),
    # normalized by k so perfect balance gives 1.0 for any top-k
    frac = onehot.sum(axis=2).mean(axis=1) / k                  # (G, E)
    mean_gate = gates.mean(axis=1)                              # (G, E)
    aux = (frac * mean_gate).sum(axis=-1).mean() * E
    return combine.astype(jnp.float32), dispatch, aux


def gather_dispatch(logits: jax.Array, cfg: ArchConfig, cap: int):
    """Gather/scatter routing (beyond-paper §Perf): returns
    (token_idx (G,E,C) int32, weight (G,E,C) f32, aux).

    Equivalent routing decision to :func:`router_dispatch` but realized as a
    sort + gather instead of one-hot einsums — zero dispatch FLOPs. Priority
    is choice-major then token order, matching the einsum path.
    """
    G, gs, E = logits.shape
    k = cfg.experts_top_k
    gates = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(gates, k)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

    # flat choices in choice-major priority order: index c*gs + s
    eid = topi.transpose(0, 2, 1).reshape(G, k * gs)        # (G, k*gs)
    wgt = topv.transpose(0, 2, 1).reshape(G, k * gs)
    order = jnp.argsort(eid, axis=1, stable=True)           # by expert, prio
    eid_sorted = jnp.take_along_axis(eid, order, axis=1)
    counts = jnp.sum(jax.nn.one_hot(eid, E, dtype=jnp.int32), axis=1)
    starts = jnp.cumsum(counts, axis=1) - counts            # exclusive (G,E)
    slot_pos = starts[:, :, None] + jnp.arange(cap)[None, None]  # (G,E,C)
    valid = jnp.arange(cap)[None, None] < counts[:, :, None]
    slot_pos = jnp.clip(slot_pos, 0, k * gs - 1)
    flat_choice = jnp.take_along_axis(
        order, slot_pos.reshape(G, E * cap), axis=1)        # (G, E*C)
    token_idx = (flat_choice % gs).reshape(G, E, cap).astype(jnp.int32)
    weight = jnp.take_along_axis(wgt, flat_choice, axis=1) \
        .reshape(G, E, cap) * valid

    frac = jnp.sum(jax.nn.one_hot(topi, E, dtype=jnp.float32),
                   axis=2).mean(axis=1) / k
    aux = (frac * gates.mean(axis=1)).sum(axis=-1).mean() * E
    return token_idx, weight.astype(jnp.float32), aux


def moe_mlp(x: jax.Array, p: Dict[str, jax.Array], cfg: ArchConfig,
            rt: RuntimeCfg = DEFAULT_RT) -> Tuple[jax.Array, jax.Array]:
    """MoE feed-forward. x: (B, S, d) -> (out, aux_loss).

    Expert weights: p["w_gate"|"w_up"]: (E, d, f); p["w_down"]: (E, f, d);
    p["router"]: (d, E); optional p["shared"]: dense SwiGLU params.
    """
    b, s, d = x.shape
    E = cfg.num_experts
    gs = min(cfg.moe_group_size, b * s)
    T = b * s
    assert T % gs == 0, (T, gs)
    G = T // gs
    cap = capacity(cfg, gs)

    # token groups shard over every mesh axis (batch·seq product); the
    # dispatch einsum output then reshards to expert-parallel layout — GSPMD
    # emits the canonical MoE all-to-all between the two constraints.
    xt = shard_tag(rt, x.reshape(G, gs, d), "moe_tokens")
    # HIGHEST: at the TPU's default precision an f32 dot rounds its
    # operands to bf16, and the router's top-k is what that would perturb
    logits = jnp.einsum("gsd,de->gse", xt.astype(jnp.float32),
                        p["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)

    if rt.moe_gather_dispatch:
        token_idx, weight, aux = gather_dispatch(logits, cfg, cap)
        xin = jnp.take_along_axis(
            xt, token_idx.reshape(G, E * cap)[..., None], axis=1) \
            .reshape(G, E, cap, d)
    else:
        combine, dispatch, aux = router_dispatch(logits, cfg, cap)
        # dispatch tokens to expert capacity slots: (G, E, C, d)
        xin = batched_einsum("gsec,gsd->gecd", dispatch.astype(x.dtype), xt,
                             rt)
    xin = shard_tag(rt, xin, "moe_dispatch")

    # expert SwiGLU: (G, E, C, d) x (E, d, f)
    from repro.core import execution as ex
    pol = ex.policy_from(cfg, rt)

    def edot(a, w):
        """Per-expert matmul through the registry; FP8 applies per-expert
        dynamic scaling (one scale per expert weight, matching the paper's
        per-tensor recipe at expert granularity). bf16 experts also route
        per-expert when a Pallas backend is selected — otherwise the
        batched einsum IS the jnp backend and stays fused."""
        if pol.precision == "fp8" or pol.backend.startswith("pallas"):
            if rt.f32_batched_dots:
                # CPU execution: unrolled per-expert plain dots (supported)
                outs = [ex.matmul(a[:, e], w[e], pol, out_dtype=rt.act_dtype)
                        for e in range(w.shape[0])]
                return jnp.stack(outs, axis=1)
            return jax.vmap(lambda ai, wi: ex.matmul(
                ai, wi, pol, out_dtype=rt.act_dtype),
                in_axes=(1, 0), out_axes=1)(a, w)
        return batched_einsum("gecx,exf->gecf", a, w, rt)

    gate = edot(xin, p["w_gate"])
    up = edot(xin, p["w_up"])
    hmid = jax.nn.silu(gate.astype(jnp.float32)).astype(rt.act_dtype) * up
    down = edot(hmid, p["w_down"])

    # combine back: (G, gs, d)
    if rt.moe_gather_dispatch:
        contrib = (down.astype(jnp.float32)
                   * weight[..., None]).reshape(G, E * cap, d)
        gidx = jnp.arange(G)[:, None]
        out = jnp.zeros((G, gs, d), jnp.float32) \
            .at[gidx, token_idx.reshape(G, E * cap)].add(contrib) \
            .astype(x.dtype)
    else:
        out = batched_einsum("gsec,gecd->gsd", combine, down, rt,
                             out_dtype=x.dtype)
    out = out.reshape(b, s, d)

    if cfg.moe_shared_expert and "shared" in p:
        out = out + swiglu_mlp(x, p["shared"], cfg, rt)
    return out, aux.astype(jnp.float32)


def init_moe(key, cfg: ArchConfig, dtype):
    d, f, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    p = {
        "router": _init(k1, (d, E), jnp.float32),
        "w_gate": _init(k2, (E, d, f), dtype),
        "w_up": _init(k3, (E, d, f), dtype),
        "w_down": _init(k4, (E, f, d), dtype),
    }
    if cfg.moe_shared_expert:
        from repro.models.layers import init_mlp
        p["shared"] = init_mlp(k5, cfg, dtype)
    return p
