"""Serving: prefill/decode step builders + continuous batching manager.

``make_serve_step``/``make_prefill_step`` produce the jittable functions the
dry-run lowers for the ``decode_*``/``prefill_*`` shapes. ``ServeSession``
implements paper-§9.2-style continuous batching on top ("vLLM-style,
requires ≥32 concurrent users" — the occupancy lever for FP8 serving):
requests join/leave slots between steps, each slot advances at its own
position, and FP8/2:4 weight compression applies per the configured policy.

Multi-tenant admission/fairness policy lives one layer up in
:mod:`repro.runtime.scheduler`; this module owns the slot mechanics it
builds on (``admit`` / ``decode_once`` / ``free_slot``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core import concurrency as cc
from repro.core import execution as ex
from repro.core import paging
from repro.core import speculative as spv
from repro.models import (
    PAGED_KINDS, decode_step, init_cache, init_paged_cache, prefill)
from repro.models.transformer import paged_decode_step
from repro.models.layers import RuntimeCfg, DEFAULT_RT
from repro.runtime.telemetry import span


def make_prefill_step(cfg: ArchConfig, rt: RuntimeCfg = DEFAULT_RT,
                      policy: Optional[ex.ExecutionPolicy] = None):
    if policy is not None:
        cfg, rt = ex.apply_policy(cfg, rt, policy)

    def prefill_step(params, inputs):
        return prefill(params, inputs, cfg, rt)
    return prefill_step


def make_serve_step(cfg: ArchConfig, rt: RuntimeCfg = DEFAULT_RT,
                    temperature: float = 0.0,
                    policy: Optional[ex.ExecutionPolicy] = None):
    """serve_step(params, tokens (B,1), caches, pos, rng) ->
    (next_tokens (B,1), logits, new_caches). ``pos`` is a scalar (lockstep)
    or a (B,) vector (continuous batching: per-slot positions)."""
    if policy is not None:
        cfg, rt = ex.apply_policy(cfg, rt, policy)

    def serve_step(params, tokens, caches, pos, rng):
        logits, new_caches = decode_step(params, tokens, caches, pos, cfg, rt)
        if temperature > 0:
            nxt = jax.random.categorical(rng, logits / temperature, axis=-1)
        else:
            nxt = jnp.argmax(logits, axis=-1)
        return nxt[:, None].astype(jnp.int32), logits, new_caches
    return serve_step


def make_paged_serve_step(cfg: ArchConfig, rt: RuntimeCfg = DEFAULT_RT,
                          temperature: float = 0.0,
                          policy: Optional[ex.ExecutionPolicy] = None):
    """``make_serve_step`` over the paged cache layout: the step takes an
    extra ``page_map`` (B, max_pages) int32 operand (``-1`` = unallocated)
    and routes PAGED_KINDS attention through the pooled pages. Greedy
    sampling is identical — paged decode is bit-exact vs dense."""
    if policy is not None:
        cfg, rt = ex.apply_policy(cfg, rt, policy)

    def paged_serve_step(params, tokens, caches, pos, page_map, rng):
        logits, new_caches = paged_decode_step(params, tokens, caches, pos,
                                               page_map, cfg, rt)
        if temperature > 0:
            nxt = jax.random.categorical(rng, logits / temperature, axis=-1)
        else:
            nxt = jnp.argmax(logits, axis=-1)
        return nxt[:, None].astype(jnp.int32), logits, new_caches
    return paged_serve_step


# ---------------------------------------------------------------------------
# Continuous batching (host-side slot manager)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray               # (Lp,) int32
    max_new: int
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # Telemetry (filled by ServeSession/StreamScheduler; wall-clock seconds
    # from perf_counter, step indices in scheduler virtual time).
    tenant: Optional[str] = None
    submit_t: float = 0.0
    admit_t: float = 0.0
    finish_t: float = 0.0
    submit_step: int = -1
    admit_step: int = -1
    finish_step: int = -1

    @property
    def latency_s(self) -> float:
        return max(0.0, self.finish_t - self.submit_t)

    @property
    def queue_wait_s(self) -> float:
        return max(0.0, self.admit_t - self.submit_t)


@dataclasses.dataclass
class SlotExport:
    """One in-flight request's complete per-slot serving state, detached
    from its session: the KV/SSM cache slice (every cache leaf indexed at
    the slot's batch row), the slot-local write position, and the last
    sampled token (the next decode input). Produced by
    :meth:`ServeSession.export_slot`, consumed by
    :meth:`ServeSession.import_slot` — the live-migration cache handoff.
    Greedy decode resumes bit-exactly on the importing session as long as
    both sessions share (cfg, max_len) and an execution-compatible policy;
    sampled (temperature > 0) decode follows the importing session's RNG
    stream instead."""
    request: Request
    caches: Any                      # pytree: leaf shapes (n_layer, ...)
    pos: int
    token: int
    # Paged handoff metadata (0/0 on dense exports): paged leaves in
    # ``caches`` are shaped (n_layer, pages, page_size, ...) — only the
    # pages the slot actually wrote travel, so handoff volume is
    # O(pages-in-use), not O(max_len).
    pages: int = 0
    page_size: int = 0


def export_nbytes(export: SlotExport) -> int:
    """Bytes of cache state a handoff moves (the fig20 migration metric)."""
    return sum(int(np.prod(l.shape)) * l.dtype.itemsize
               for l in jax.tree_util.tree_leaves(export.caches))


# Jitted step cache: sessions sharing (cfg, rt, temperature) share the
# compiled serve/prefill functions instead of re-tracing per session (the
# scheduler tests spin up many short-lived sessions over one tiny model).
# LRU-capped: a sweep over configs/policies/backends would otherwise pin
# every compiled step it ever built for the life of the process.
_JIT_CACHE: "OrderedDict[Any, Any]" = OrderedDict()
JIT_CACHE_MAX = 16


def clear_jit_cache() -> None:
    """Drop every cached jitted serve/prefill step (tests, sweeps)."""
    _JIT_CACHE.clear()


def _cached_jit(kind: str, maker: Callable[[], Callable], *key_parts):
    try:
        key = (kind,) + key_parts
        hash(key)
    except TypeError:                 # unhashable cfg/rt (e.g. shard_fn)
        return jax.jit(maker())
    fn = _JIT_CACHE.get(key)
    if fn is None:
        fn = _JIT_CACHE[key] = jax.jit(maker())
    _JIT_CACHE.move_to_end(key)
    while len(_JIT_CACHE) > JIT_CACHE_MAX:
        _JIT_CACHE.popitem(last=False)
    return fn


# Cache-leaf classification for slot writes: attention leaves are row-per-
# position (axis 2 after the layer-stack dim), state leaves (mamba2 h/conv,
# rwkv6 S/prev_*) are whole-slot values. (rwkv6's "S" is uppercase — no
# collision with the attention keys.)
_SEQ_LEAVES = ("k", "v", "pos")


def _leaf_key(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "idx", "")))


@functools.partial(jax.jit, donate_argnums=(0,))
def _write_slot_cache(full, new, slot):
    """Insert a batch-1 prefill cache into ``slot`` of a batched session
    cache: k/v/pos write their first S rows (the prompt's positions), state
    leaves replace the slot wholesale. Jitted with the session cache
    donated so the update happens in place instead of copying every cache
    leaf per admission."""
    def write(path, f, n):
        row = n[:, 0]                             # drop the batch-1 dim
        if _leaf_key(path) in _SEQ_LEAVES:
            s = row.shape[1]
            return f.at[:, slot, :s].set(row.astype(f.dtype))
        return f.at[:, slot].set(row.astype(f.dtype))
    return jax.tree_util.tree_map_with_path(write, full, new)


@functools.partial(jax.jit, donate_argnums=(0,))
def _restore_slot_cache(full, state, slot):
    """Write one exported slot's cache state (every leaf already sliced to
    its slot row, full max_len for k/v/pos) wholesale into ``slot`` of a
    batched session cache — the receiving half of a live cache handoff.
    Jitted + donated like :func:`_write_slot_cache`."""
    return jax.tree_util.tree_map(
        lambda f, s: f.at[:, slot].set(s.astype(f.dtype)), full, state)


@functools.partial(jax.jit, donate_argnums=(0,))
def _clear_slot_cache(caches, slot):
    """Reset ``slot`` to its init_cache state: k/v zeroed, pos rows -1
    (the decode mask treats them as unwritten), SSM/linear-attention state
    zeroed. A freed slot keeps NOTHING of its previous occupant — slot
    reuse must never attend to stale keys/values. Jitted + donated like
    :func:`_write_slot_cache` (slot free is on the serving hot path)."""
    def clear(path, f):
        if _leaf_key(path) == "pos":
            return f.at[:, slot].set(-1)
        return f.at[:, slot].set(jnp.zeros((), f.dtype))
    return jax.tree_util.tree_map_with_path(clear, caches)


# -- paged-cache twins of the slot helpers ----------------------------------
# Paged leaves live under caches["layers"]["b{i}"] for PAGED_KINDS blocks,
# pooled as (n_super, n_pages+1, page_size, ...); everything else (window
# caches, SSM state, tail) keeps the dense slot-indexed layout and is
# handled exactly like the dense helpers above. ``phys`` vectors are padded
# to the per-slot table width with the trash-page index so the jitted
# scatters have a fixed shape — trash writes only ever carry scrub values.

def _paged_blocks(pat) -> frozenset:
    return frozenset(f"b{i}" for i, kind in enumerate(pat)
                     if kind in PAGED_KINDS)


def _is_paged_leaf(path, paged_blocks) -> bool:
    if len(path) < 3:
        return False
    root = str(getattr(path[0], "key", ""))
    blk = str(getattr(path[1], "key", ""))
    return (root == "layers" and blk in paged_blocks
            and _leaf_key(path) in _SEQ_LEAVES)


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
def _paged_write_prompt(pat, full, new, slot, phys):
    """Paged ``_write_slot_cache``: the batch-1 prefill cache's seq rows
    are padded to ``max_len`` (k/v with zeros, pos with -1 — exactly the
    scrubbed-page values), split into pages, and scattered to the slot's
    physical pages. ``phys`` is (max_pages,) int32, unallocated entries
    pointing at the trash page (they carry pure padding, so the duplicate
    trash writes are deterministic)."""
    paged = _paged_blocks(pat)

    def write(path, f, n):
        row = n[:, 0]                             # drop the batch-1 dim
        if _is_paged_leaf(path, paged):
            ps = f.shape[2]
            mp = phys.shape[0]
            s = row.shape[1]
            pad_shape = (row.shape[0], mp * ps - s) + row.shape[2:]
            if _leaf_key(path) == "pos":
                fill = jnp.full(pad_shape, -1, row.dtype)
            else:
                fill = jnp.zeros(pad_shape, row.dtype)
            slab = jnp.concatenate([row, fill], axis=1).reshape(
                (row.shape[0], mp, ps) + row.shape[2:])
            return f.at[:, phys].set(slab.astype(f.dtype))
        if _leaf_key(path) in _SEQ_LEAVES:
            s = row.shape[1]
            return f.at[:, slot, :s].set(row.astype(f.dtype))
        return f.at[:, slot].set(row.astype(f.dtype))
    return jax.tree_util.tree_map_with_path(write, full, new)


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
def _paged_clear_slot(pat, caches, slot, phys):
    """Paged ``_clear_slot_cache``: scrub the slot's released physical
    pages back to their init state (k/v zeroed, pos -1) *before* the
    allocator reuses them — free-list reuse can never leak a previous
    tenant's KV — and clear the slot's dense (state/window) leaves."""
    paged = _paged_blocks(pat)

    def clear(path, f):
        if _is_paged_leaf(path, paged):
            ps = f.shape[2]
            mp = phys.shape[0]
            shape = (f.shape[0], mp, ps) + f.shape[3:]
            if _leaf_key(path) == "pos":
                return f.at[:, phys].set(jnp.full(shape, -1, f.dtype))
            return f.at[:, phys].set(jnp.zeros(shape, f.dtype))
        if _leaf_key(path) == "pos":
            return f.at[:, slot].set(-1)
        return f.at[:, slot].set(jnp.zeros((), f.dtype))
    return jax.tree_util.tree_map_with_path(clear, caches)


def _paged_take_slot(pat, caches, slot, idx):
    """Gather one slot's state for export: paged leaves as the slot's
    pages-in-use only (n_super, n_used, page_size, ...), dense leaves as
    the slot row. ``idx`` is the (n_used,) int32 page-id array. Unjitted —
    handoffs are rare and variable-sized."""
    paged = _paged_blocks(pat)

    def take(path, f):
        if _is_paged_leaf(path, paged):
            return f[:, idx]
        return f[:, slot]
    return jax.tree_util.tree_map_with_path(take, caches)


def _paged_put_slot(pat, caches, state, slot, idx):
    """Scatter an exported slot's state into freshly allocated pages
    (paged leaves) and the slot row (dense leaves) — the receiving half
    of an O(pages) handoff. ``idx`` is the (n_used,) int32 page-id array."""
    paged = _paged_blocks(pat)

    def put(path, f, s):
        if _is_paged_leaf(path, paged):
            return f.at[:, idx].set(s.astype(f.dtype))
        return f.at[:, slot].set(s.astype(f.dtype))
    return jax.tree_util.tree_map_with_path(put, caches, state)


@dataclasses.dataclass
class DecodeTicket:
    """One in-flight decode step: dispatched through an ExecutionLane but
    not yet joined. ``handle`` is None when the session had no active
    slots (nothing was enqueued; only ``oom_done`` carries information).
    Produced by :meth:`ServeSession.dispatch_decode`, consumed exactly
    once by :meth:`ServeSession.join_decode`."""
    handle: Optional[cc.LaneHandle]
    oom_done: List["Request"]
    lane: str = ""
    overlap_group: int = -1
    t0: float = 0.0
    # Speculative decode: the depth this step ran at (1 = plain decode)
    # and the draft chain's own lane handle (telemetry; the verify thunk
    # already consumes its result as an XLA data dependency).
    spec_k: int = 1
    draft_handle: Optional[cc.LaneHandle] = None


class ServeSession:
    """Fixed-slot continuous batching over a single shared KV cache.

    Each slot advances at its OWN position (``decode_step`` takes a (B,)
    position vector): admission is one bulk prefill (``make_prefill_step``)
    written into the slot's cache rows — active slots are untouched and
    lose no output — and a freed slot's cache rows are cleared before
    reuse. The first generated token is sampled from the prefill logits,
    so admission itself emits output token #1.

    ``submit``/``step``/``run`` drive a single FIFO queue; the multi-tenant
    scheduler (:mod:`repro.runtime.scheduler`) instead calls the slot-level
    API directly: ``has_free_slot`` → ``admit(req)`` → ``decode_once()``.

    ``device`` pins the session's serving state (caches, page map, token
    buffer, positions, RNG) to one device, the one its ``params`` live on;
    ``None`` leaves it on JAX's default device.
    """

    def __init__(self, params, cfg: ArchConfig, *, batch_slots: int,
                 max_len: int, rt: RuntimeCfg = DEFAULT_RT,
                 temperature: float = 0.0, eos_id: int = -1, seed: int = 0,
                 policy=None, auto_backend: Optional[str] = None,
                 verbose_policy: bool = False, telemetry=None,
                 paged: bool = False, page_size: int = 16,
                 pages: Optional[int] = None, speculative=None,
                 device=None):
        self.device = device
        # Speculative decoding rides on the greedy-exactness contract:
        # the verify pass accepts drafts by argmax comparison, so a
        # sampling session has no exact acceptance rule. Refuse up front
        # (the kill switch is SpecDecodeSpec(k=1) or speculative=None).
        self.speculative = spv.SpecDecodeSpec.from_any(speculative)
        if self.speculative is not None and temperature > 0:
            raise ValueError(
                "speculative decoding is greedy-only (temperature == 0): "
                "verify-by-argmax has no exact acceptance rule for "
                f"sampled decode (temperature={temperature})")
        # The draft chain may need the unpacked weights (a dense-layout
        # draft policy under a sparse24 session policy): keep the raw
        # reference from before any pack.
        raw_params = params
        if policy == "auto":
            # paper-§9.2 resolution at session construction: the dominant
            # decode GEMM is (slots, d_model, d_ff); decode is
            # latency-sensitive and each slot is a tenant.
            policy = ex.resolve_policy(
                batch_slots, cfg.d_model, cfg.d_ff,
                precision=cfg.precision, latency_sensitive=True,
                tenants=batch_slots, backend=auto_backend)
        if policy is not None:
            cfg, rt = ex.apply_policy(cfg, rt, policy)
            if policy.sparsity == "sparse24":
                # serving form of 2:4: prune+pack ONCE here so decode
                # streams packed weights (the §7 bandwidth win), instead
                # of re-pruning inside every jitted step
                params = ex.pack_model_params(params)
            if verbose_policy:
                print(f"[serve] policy: {policy.describe()}")
        self.policy = policy
        # telemetry: a repro.runtime.telemetry.Tracer (duck-typed) that
        # receives per-op serving events (prefill/decode wall times).
        self.tracer = telemetry
        self.params = params
        self.cfg = cfg
        self.rt = rt
        self.batch_slots = batch_slots
        self.max_len = max_len
        self.eos_id = eos_id
        self.temperature = temperature
        self.slots: List[Optional[Request]] = [None] * batch_slots
        self._pat = cfg.superlayer_pattern
        self.paged = bool(paged)
        # The ambient default policy/backend is resolved at trace time by
        # dense() whenever rt.policy is unset, so it must be part of the
        # cache key — a --backend sweep flips it between sessions. Page
        # geometry is part of the key too: a different --page-size changes
        # the cache layout the step was traced for.
        ambient = ex.get_default_policy()
        if self.paged:
            if max_len % page_size:
                raise ValueError(f"max_len={max_len} must be a multiple of "
                                 f"page_size={page_size}")
            # register the paged-decode kernel backend (telemetry naming)
            from repro.kernels import paged_attention  # noqa: F401
            mp = max_len // page_size
            if pages is None:
                pages = batch_slots * mp      # dense-equivalent capacity
            self.page_size, self.pages = int(page_size), int(pages)
            self.pager = paging.PageAllocator(
                self.pages, self.page_size, mp, batch_slots,
                state_block_tokens=paging.state_block_tokens(cfg))
            with self._on_device():
                self.caches = self._put(init_paged_cache(
                    cfg, batch_slots, max_len, self.page_size, self.pages))
            self._page_map = self._put(self.pager.page_map())
            self.step_fn = _cached_jit(
                "serve_paged",
                lambda: make_paged_serve_step(cfg, rt, temperature),
                cfg, rt, temperature, ambient, self.page_size, self.pages)
        else:
            self.page_size, self.pages = 0, 0
            self.pager = None
            with self._on_device():
                self.caches = self._put(init_cache(cfg, batch_slots, max_len))
            self.step_fn = _cached_jit(
                "serve", lambda: make_serve_step(cfg, rt, temperature),
                cfg, rt, temperature, ambient)
        # next write position per slot (slot-local: every request starts
        # at position 0 regardless of when it was admitted)
        self.slot_pos = np.zeros((batch_slots,), np.int32)
        self.prefill_fn = _cached_jit(
            "prefill", lambda: make_prefill_step(cfg, rt), cfg, rt, ambient)
        self.rng = self._put(jax.random.PRNGKey(seed))
        self.tokens = self._put(np.zeros((batch_slots, 1), np.int32))
        self.queue: List[Request] = []
        self.completed: List[Request] = []
        self._inflight: Optional[DecodeTicket] = None
        # -- speculative decode state ----------------------------------
        self._spec_fns: Dict[int, Tuple[Callable, Callable]] = {}
        self._spec_deltas: List[Tuple[str, int, int]] = []
        self.spec_totals: Dict[str, Dict[str, int]] = {}
        self.adaptive_k: Optional[spv.AdaptiveK] = None
        self._draft_params = None
        if self.speculative is not None:
            dpol = self.speculative.resolved()
            if dpol.sparsity == "sparse24":
                # share the session's already-packed weights when both
                # policies are sparse24; otherwise pack a draft copy once
                if isinstance(self.policy, ex.ExecutionPolicy) \
                        and self.policy.sparsity == "sparse24":
                    self._draft_params = self.params
                else:
                    self._draft_params = ex.pack_model_params(raw_params)
            else:
                self._draft_params = raw_params
            if self.speculative.adaptive:
                self.adaptive_k = spv.AdaptiveK(self.speculative)

    # -- device placement ---------------------------------------------------
    def _on_device(self):
        """Create arrays directly on the session's device."""
        if self.device is None:
            return contextlib.nullcontext()
        return jax.default_device(self.device)

    def _put(self, x):
        """Host values (or a pytree of arrays) onto the session's device."""
        if self.device is None:
            return jax.tree_util.tree_map(jnp.asarray, x)
        return jax.device_put(x, self.device)

    # -- slot-level API (used by the scheduler) ----------------------------
    def _policy_scope(self):
        """Partition-local policy scope around every prefill/decode call:
        trace-time consumers that would fall back to the ambient default
        policy resolve THIS session's policy instead — under heterogeneous
        per-partition policies the ambient default belongs to no one."""
        if isinstance(self.policy, ex.ExecutionPolicy):
            return ex.policy_scope(self.policy)
        return contextlib.nullcontext()

    def _policy_tag(self) -> Dict[str, str]:
        """Event attribution for this session's serving ops."""
        if isinstance(self.policy, ex.ExecutionPolicy):
            return {"policy": self.policy.spec(),
                    "backend": self.policy.backend}
        return {}

    @property
    def n_active(self) -> int:
        return sum(s is not None for s in self.slots)

    def has_free_slot(self) -> bool:
        return any(s is None for s in self.slots)

    def free_slots(self) -> int:
        return sum(s is None for s in self.slots)

    def can_admit(self, req: Request) -> bool:
        """Admission headroom: a free slot AND (paged) enough free pages
        for the prompt plus its first decode write. The dense path is
        exactly ``has_free_slot`` — slots ARE the capacity unit there."""
        if not self.has_free_slot():
            return False
        if not self.paged:
            return True
        return self.pager.can_admit_tokens(len(req.prompt) + 1)

    def _phys_padded(self, page_ids: List[int]) -> jax.Array:
        """(max_pages,) int32 scatter vector: the slot's physical pages,
        padded with the trash-page index (fixed shape → one jitted trace)."""
        mp = self.pager.max_pages_per_slot
        trash = self.pages                        # pool row past the last page
        out = np.full((mp,), trash, np.int32)
        out[:len(page_ids)] = page_ids
        return self._put(out)

    def _sync_page_map(self) -> None:
        self._page_map = self._put(self.pager.page_map())

    def admit(self, req: Request) -> int:
        """Bulk-prefill ``req`` into a free slot and sample its first
        output token from the prefill logits. Active slots do not step —
        admission can never drop another request's tokens. Returns the
        slot index (the request may already be done if ``max_new == 1``)."""
        with span(self.tracer, "session.admit", uid=req.uid):
            return self._admit(req)

    def _admit(self, req: Request) -> int:
        slot = next((i for i, s in enumerate(self.slots) if s is None), None)
        if slot is None:
            raise RuntimeError("admit() with no free slot")
        lp = len(req.prompt)
        if not 0 < lp < self.max_len:
            raise ValueError(f"prompt length {lp} not in [1, {self.max_len})")
        if self.paged:
            # reserve pages BEFORE the prefill: lp prompt positions plus
            # the first decode write at position lp. Raises PagesExhausted
            # (admission refused) — callers gate on can_admit() first.
            page_ids = self.pager.alloc_slot(slot, lp + 1)
        prompt = self._put(np.asarray(req.prompt, np.int32)[None, :])
        with span(self.tracer, "session.prefill.wait"):
            t0 = time.perf_counter()
            with self._policy_scope():
                logits, pcaches = self.prefill_fn(self.params, prompt)
            if self.tracer is not None:
                jax.block_until_ready(logits)
            wall_s = time.perf_counter() - t0
        if self.tracer is not None:
            self.tracer.record(
                "prefill", m=lp, k=self.cfg.d_model, n=self.cfg.d_ff,
                precision=self.cfg.precision, **self._policy_tag(),
                wall_s=wall_s, tenant=req.tenant or "",
                meta={"uid": req.uid, "slot": slot})
        if self.paged:
            self.caches = _paged_write_prompt(
                self._pat, self.caches, pcaches, slot,
                self._phys_padded(page_ids))
            self._sync_page_map()
            self.pager.record(self.tracer, phase="admit", slot=slot,
                              tenant=req.tenant or "", uid=req.uid)
        else:
            self.caches = _write_slot_cache(self.caches, pcaches, slot)
        with span(self.tracer, "session.first_token.wait"):
            if self.temperature > 0:
                self.rng, sub = jax.random.split(self.rng)
                tok = int(jax.random.categorical(
                    sub, logits[0] / self.temperature))
            else:
                tok = int(jnp.argmax(logits[0]))
        self.slots[slot] = req
        self.slot_pos[slot] = lp
        self.tokens = self.tokens.at[slot, 0].set(tok)
        req.admit_t = time.perf_counter()
        req.out.append(tok)
        self._maybe_finish(slot, tok)
        return slot

    def free_slot(self, slot: int):
        self.slots[slot] = None
        self.slot_pos[slot] = 0
        if self.paged:
            released = self.pager.free_slot(slot)
            # scrub the released pages BEFORE the free list hands them out
            self.caches = _paged_clear_slot(self._pat, self.caches, slot,
                                            self._phys_padded(released))
            self._sync_page_map()
            self.pager.record(self.tracer, phase="free", slot=slot)
        else:
            self.caches = _clear_slot_cache(self.caches, slot)
        self.tokens = self.tokens.at[slot, 0].set(0)

    # -- live cache handoff (tenant migration) ------------------------------
    def export_slot(self, slot: int) -> SlotExport:
        """Detach ``slot``'s in-flight request with its complete serving
        state (cache slice, position, next-token input) and clear the slot
        — the request is NOT finished; it resumes wherever the export is
        imported. The slot is left exactly as :meth:`free_slot` leaves it,
        so the next occupant cannot attend to the emigrant's KV rows."""
        req = self.slots[slot]
        if req is None:
            raise ValueError(f"slot {slot} is empty")
        # Materialize the slices BEFORE _clear_slot_cache donates the
        # session buffers: these are fresh arrays, not views.
        if self.paged:
            page_ids = self.pager.slot_pages(slot)
            state = _paged_take_slot(self._pat, self.caches, slot,
                                     self._put(np.asarray(page_ids, np.int32)))
            out = SlotExport(request=req, caches=state,
                             pos=int(self.slot_pos[slot]),
                             token=int(self.tokens[slot, 0]),
                             pages=len(page_ids), page_size=self.page_size)
            if self.tracer is not None:
                self.pager.record(self.tracer, phase="export", slot=slot,
                                  tenant=req.tenant or "",
                                  pages_moved=len(page_ids),
                                  handoff_bytes=export_nbytes(out))
        else:
            state = jax.tree_util.tree_map(lambda f: f[:, slot], self.caches)
            out = SlotExport(request=req, caches=state,
                             pos=int(self.slot_pos[slot]),
                             token=int(self.tokens[slot, 0]))
        jax.block_until_ready(state)
        self.free_slot(slot)
        return out

    def handoff_pages(self, slot: int) -> int:
        """Pages a migration of ``slot`` would move (0 on dense sessions —
        dense handoffs move the whole max_len slice regardless)."""
        return len(self.pager.slot_pages(slot)) if self.paged else 0

    def can_accept_pages(self, n_pages: int, page_size: int) -> bool:
        """Import-side headroom check *before* the exporter detaches the
        slot: free slot, and on paged sessions matching page geometry plus
        enough free pages for the ``n_pages`` the handoff would move."""
        if not self.has_free_slot():
            return False
        if not self.paged:
            return True
        return (page_size == self.page_size
                and n_pages <= self.pager.max_pages_per_slot
                and self.pager.can_alloc(n_pages))

    def can_accept_handoff(self, export: SlotExport) -> bool:
        """Would :meth:`import_slot` succeed right now?"""
        return self.can_accept_pages(export.pages, export.page_size)

    def import_slot(self, export: SlotExport) -> int:
        """Resume an exported in-flight request in a free slot of THIS
        session. Sessions must share the cache layout — same config and
        ``max_len`` (checked leaf-by-leaf). Returns the slot index."""
        slot = next((i for i, s in enumerate(self.slots) if s is None), None)
        if slot is None:
            raise RuntimeError("import_slot() with no free slot")
        # a handoff between partitions on different chips moves the slot
        # state onto this session's device before it is scattered in
        state = self._put(export.caches)
        if self.paged != bool(export.pages or export.page_size):
            raise ValueError(
                "cache layout mismatch: paged and dense sessions cannot "
                "hand off slots to each other")
        if self.paged:
            if export.page_size != self.page_size:
                raise ValueError(
                    f"page_size mismatch: export {export.page_size} vs "
                    f"session {self.page_size}")
            # Both sides paged: paged leaves compare trailing (page
            # geometry) dims — the export carries pages-in-use, not the
            # full pool — dense state leaves compare whole slot slices.
            paged_blocks = _paged_blocks(self._pat)
            ours: List[tuple] = []
            theirs: List[tuple] = []

            def collect(path, f, s):
                ours.append(f.shape[:1] + f.shape[2:])
                theirs.append(s.shape[:1] + s.shape[2:]
                              if _is_paged_leaf(path, paged_blocks)
                              else s.shape)
                return f
            jax.tree_util.tree_map_with_path(collect, self.caches, state)
            if ours != theirs:
                raise ValueError(
                    "cache layout mismatch: the exporting session's slot "
                    "state does not fit this session (same cfg, max_len "
                    "and page_size required for a live handoff)")
            # May raise PagesExhausted — callers gate on
            # can_accept_handoff() first.
            page_ids = self.pager.import_slot(slot, export.pages,
                                              export.pos + 1)
            self.caches = _paged_put_slot(
                self._pat, self.caches, state, slot,
                self._put(np.asarray(page_ids, np.int32)))
            self._sync_page_map()
            self.pager.record(self.tracer, phase="import", slot=slot,
                              tenant=export.request.tenant or "",
                              pages_moved=export.pages)
        else:
            ours = [f.shape[:1] + f.shape[2:]
                    for f in jax.tree_util.tree_leaves(self.caches)]
            theirs = [s.shape for s in jax.tree_util.tree_leaves(state)]
            if ours != theirs:
                raise ValueError(
                    "cache layout mismatch: the exporting session's slot "
                    "state does not fit this session (same cfg and max_len "
                    "required for a live handoff)")
            self.caches = _restore_slot_cache(self.caches, state, slot)
        self.slots[slot] = export.request
        self.slot_pos[slot] = export.pos
        self.tokens = self.tokens.at[slot, 0].set(export.token)
        return slot

    # -- speculative decode plumbing ----------------------------------------
    def _next_spec_k(self) -> int:
        """Depth for the next decode step: the spec's k, or the adaptive
        controller's current actuation (floor 1 = drafting disabled)."""
        if self.speculative is None:
            return 1
        if self.adaptive_k is not None:
            return max(1, min(self.adaptive_k.k, self.speculative.k))
        return self.speculative.k

    def _spec_fns_for(self, k: int) -> Tuple[Callable, Callable]:
        """Jitted (draft, verify) pair for depth ``k``.

        The speculative geometry — the draft policy's full spec AND k —
        is part of the draft jit key: k and the policy are baked into the
        trace, so two sessions differing only in speculative geometry
        must not share a compiled draft chain. Audit of the remaining
        ``ServingSpec``-derived key components: cfg/rt (session policy
        applied), the ambient default policy, temperature (speculation is
        greedy-only, so the verify excludes it by construction), and page
        geometry are already in the plain-step keys; ``batch_slots`` /
        ``max_len`` / k-as-operand-width only change traced *shapes*,
        which one ``jax.jit`` re-traces per shape on its own."""
        fns = self._spec_fns.get(k)
        if fns is None:
            spec = self.speculative
            dkey = spec.spec_key()
            ambient = ex.get_default_policy()
            geo = (self.page_size, self.pages) if self.paged else ()
            draft_fn = _cached_jit(
                "spec_draft",
                lambda: spv.make_draft_step(self.cfg, self.rt,
                                            spec.resolved(), k - 1,
                                            paged=self.paged),
                self.cfg, self.rt, ambient, dkey, k, self.paged, *geo)
            verify_fn = _cached_jit(
                "spec_verify",
                lambda: spv.make_verify_step(self.cfg, self.rt,
                                             paged=self.paged),
                self.cfg, self.rt, ambient, self.paged, *geo)
            fns = self._spec_fns[k] = (draft_fn, verify_fn)
        return fns

    def drain_spec_deltas(self) -> List[Tuple[str, int, int]]:
        """Hand the per-slot ``(tenant, drafted, accepted)`` samples since
        the last drain to the caller (the scheduler folds them into its
        per-tenant accounting)."""
        out, self._spec_deltas = self._spec_deltas, []
        return out

    def dispatch_decode(self, lane: Optional[cc.ExecutionLane] = None, *,
                        overlap_group: int = -1) -> DecodeTicket:
        """Dispatch half of a decode step: page bookkeeping, then enqueue
        the jitted step through ``lane`` (JAX async dispatch — the call
        returns future arrays without blocking) and hand back a
        :class:`DecodeTicket`. The session's cache references advance to
        the in-flight arrays immediately, but host state (tokens,
        positions, completions) is only touched by :meth:`join_decode` —
        so the token stream is byte-identical to the synchronous path
        regardless of what other lanes do in between."""
        with span(self.tracer, "session.dispatch"):
            return self._dispatch_decode(lane, overlap_group)

    def _dispatch_decode(self, lane: Optional[cc.ExecutionLane],
                         overlap_group: int) -> DecodeTicket:
        if self._inflight is not None:
            raise RuntimeError(
                "decode already in flight: join_decode the previous "
                "ticket before dispatching another step")
        if self.n_active == 0:
            return DecodeTicket(handle=None, oom_done=[])
        k = self._next_spec_k()
        oom_done: List[Request] = []
        if self.paged:
            with span(self.tracer, "session.pages"):
                k = self._reserve_pages(k, oom_done)
            if self.n_active == 0:
                return DecodeTicket(handle=None, oom_done=oom_done)
        self.rng, sub = jax.random.split(self.rng)
        if lane is None:
            lane = cc.ExecutionLane("session")
        t0 = time.perf_counter()
        posv = self._put(self.slot_pos)
        if k > 1:
            # draft on its own lane; the verify thunk consumes the draft
            # handle's *future* tokens (an XLA data dependency — the host
            # never materializes draft tokens), so a caller that
            # dispatches the next draft before joining this verify gets
            # draft(n+1)/verify(n) overlap on real async hardware.
            active = self._put(
                np.array([s is not None for s in self.slots], np.bool_))
            draft_fn, verify_fn = self._spec_fns_for(k)
            draft_lane = cc.ExecutionLane("draft", tracer=self.tracer)
            with self._policy_scope():
                if self.paged:
                    dthunk = functools.partial(
                        draft_fn, self._draft_params, self.tokens,
                        self.caches, posv, self._page_map)
                else:
                    dthunk = functools.partial(
                        draft_fn, self._draft_params, self.tokens,
                        self.caches, posv)
                dh = draft_lane.dispatch(dthunk, label="draft",
                                         overlap_group=overlap_group)
                tokens_seq = dh.result
                if self.paged:
                    thunk = functools.partial(
                        verify_fn, self.params, tokens_seq, self.caches,
                        posv, active, self._page_map)
                else:
                    thunk = functools.partial(
                        verify_fn, self.params, tokens_seq, self.caches,
                        posv, active)
                handle = lane.dispatch(thunk, label="decode",
                                       overlap_group=overlap_group)
            _, _, _, self.caches = handle.result
            ticket = DecodeTicket(handle=handle, oom_done=oom_done,
                                  lane=lane.name,
                                  overlap_group=overlap_group, t0=t0,
                                  spec_k=k, draft_handle=dh)
            self._inflight = ticket
            return ticket
        with self._policy_scope():
            if self.paged:
                thunk = functools.partial(
                    self.step_fn, self.params, self.tokens, self.caches,
                    posv, self._page_map, sub)
            else:
                thunk = functools.partial(
                    self.step_fn, self.params, self.tokens, self.caches,
                    posv, sub)
            handle = lane.dispatch(thunk, label="decode",
                                   overlap_group=overlap_group)
        # the cache references advance to the enqueued (future) arrays
        # now, so a later dispatch on another lane never aliases stale
        # state; nothing here blocks
        _, _, self.caches = handle.result
        ticket = DecodeTicket(handle=handle, oom_done=oom_done,
                              lane=lane.name, overlap_group=overlap_group,
                              t0=t0)
        self._inflight = ticket
        return ticket

    def _reserve_pages(self, k: int, oom_done: List[Request]) -> int:
        """Page bookkeeping before a decode step: every active slot gets
        a page for each position the step may write. Returns the step's
        speculative depth (downgraded to 1 when the pool cannot cover a
        k-deep verify); requests refused a page are finished into
        ``oom_done``."""
        if k > 1:
            # batch-wide feasibility first: a k-deep verify needs a
            # page for every candidate position. If the pool cannot
            # cover the whole batch, downgrade THIS step to plain
            # decode (k=1) instead of truncating requests that plain
            # decode could still serve.
            need = 0
            for i, req in enumerate(self.slots):
                if req is None:
                    continue
                tgt = min(int(self.slot_pos[i]) + k, self.max_len)
                need += max(0, self.pager.pages_for(tgt)
                            - len(self.pager.slot_pages(i)))
            if need > self.pager.free_pages:
                self.pager.record(self.tracer, phase="spec_downgrade",
                                  need_pages=need)
                k = 1
        # lazy page append: make sure every active slot has a page
        # for each position this step may write (k candidates on a
        # speculative step; positions past max_len route to the
        # trash page in-kernel). Pool exhaustion finishes the
        # request truncated (refused, never crashed).
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            need = min(int(self.slot_pos[i]) + k, self.max_len) \
                if k > 1 else int(self.slot_pos[i]) + 1
            if self.pager.pages_for(need) > \
                    len(self.pager.slot_pages(i)):
                try:
                    self.pager.extend_slot(i, need)
                    self._sync_page_map()
                except paging.PagesExhausted:
                    self.pager.record(self.tracer, phase="page_oom",
                                      slot=i, tenant=req.tenant or "",
                                      uid=req.uid)
                    req.done = True
                    req.finish_t = time.perf_counter()
                    self.completed.append(req)
                    self.free_slot(i)
                    oom_done.append(req)
        return k

    def join_decode(self, ticket: DecodeTicket) -> List[Request]:
        """Join half of a decode step: block on the ticket's result, then
        run the host-side token accounting exactly as the synchronous path
        did. Records the ``decode`` event with the lane/overlap-group the
        step actually ran under."""
        self._inflight = None
        if ticket.handle is None:
            return list(ticket.oom_done)
        if ticket.spec_k > 1:
            return self._join_spec(ticket)
        with span(self.tracer, "session.decode.wait"):
            nxt = ticket.handle.join()[0]
            nxt_np = np.asarray(nxt[:, 0])   # forces the step to complete
            ready = time.perf_counter()
        with span(self.tracer, "session.accounting"):
            if self.tracer is not None:
                self.tracer.record(
                    "decode", m=self.batch_slots, k=self.cfg.d_model,
                    n=self.cfg.d_ff, precision=self.cfg.precision,
                    **self._policy_tag(), wall_s=ready - ticket.t0,
                    lane=ticket.lane, overlap_group=ticket.overlap_group,
                    meta={"n_active": self.n_active})
            self.tokens = nxt
            done = list(ticket.oom_done)
            for i, req in enumerate(self.slots):
                if req is None:
                    continue
                self.slot_pos[i] += 1
                tok = int(nxt_np[i])
                req.out.append(tok)
                if self._maybe_finish(i, tok):
                    done.append(req)
                elif self.paged:
                    # utilization accounting: positions written so far
                    # plus the pending next write
                    self.pager.note_tokens(i, int(self.slot_pos[i]) + 1)
            if self.adaptive_k is not None:
                self.adaptive_k.on_step()
        return done

    def _join_spec(self, ticket: DecodeTicket) -> List[Request]:
        """Join half of a speculative step: commit the accepted prefix
        (plus the verify's own token) per slot, record acceptance
        telemetry, and — paged — trim the candidate pages the verify
        already scrubbed back to the free list."""
        with span(self.tracer, "session.decode.wait"):
            nxt, greedy, n_acc, _ = ticket.handle.join()
            g_np = np.asarray(greedy)        # forces the step to complete
            acc_np = np.asarray(n_acc)
            ready = time.perf_counter()
        with span(self.tracer, "session.accounting"):
            return self._commit_spec(ticket, nxt, g_np, acc_np, ready)

    def _commit_spec(self, ticket: DecodeTicket, nxt, g_np: np.ndarray,
                     acc_np: np.ndarray, ready: float) -> List[Request]:
        """The host half of :meth:`_join_spec`, on the joined step's
        greedy tokens and accepted counts."""
        k = ticket.spec_k
        if self.tracer is not None:
            self.tracer.record(
                "decode", m=self.batch_slots, k=self.cfg.d_model,
                n=self.cfg.d_ff, precision=self.cfg.precision,
                **self._policy_tag(), wall_s=ready - ticket.t0,
                lane=ticket.lane, overlap_group=ticket.overlap_group,
                meta={"n_active": self.n_active, "spec_k": k})
        self.tokens = nxt
        done = list(ticket.oom_done)
        trimmed = False
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            acc = int(acc_np[i])
            drafted = k - 1
            finished = False
            committed = 0
            # the accepted drafts and the verify token, in order; finish
            # mid-commit truncates exactly where plain decode would have
            # stopped (the surplus accepted tokens were never committed)
            for t in range(acc + 1):
                tok = int(g_np[i, t])
                self.slot_pos[i] += 1
                req.out.append(tok)
                committed += 1
                if self._maybe_finish(i, tok):
                    done.append(req)
                    finished = True
                    break
            tenant = req.tenant or ""
            self._spec_deltas.append((tenant, drafted, acc))
            tot = self.spec_totals.setdefault(
                tenant, {"steps": 0, "drafted": 0, "accepted": 0,
                         "committed": 0})
            tot["steps"] += 1
            tot["drafted"] += drafted
            tot["accepted"] += acc
            tot["committed"] += committed
            if self.adaptive_k is not None:
                self.adaptive_k.observe(tenant, drafted, acc)
            if self.tracer is not None:
                self.tracer.record(
                    "spec", tenant=tenant,
                    meta={"k": k, "drafted": drafted, "accepted": acc,
                          "committed": committed, "uid": req.uid})
            if not finished and self.paged:
                # release the candidate pages the rejected writes grew
                # into (the verify scrubbed them in-jit before the host
                # saw n_acc, so they re-enter the free list clean)
                if self.pager.trim_slot(i, int(self.slot_pos[i]) + 1):
                    trimmed = True
                self.pager.note_tokens(i, int(self.slot_pos[i]) + 1)
        if trimmed:
            self._sync_page_map()
        if self.adaptive_k is not None:
            self.adaptive_k.on_step()
        return done

    def decode_once(self, lane: Optional[cc.ExecutionLane] = None
                    ) -> List[Request]:
        """One decode step over the active slots (no admission); returns
        the requests that completed this step. Dispatch immediately
        followed by join — the synchronous composition of the lane seam."""
        return self.join_decode(self.dispatch_decode(lane))

    def _maybe_finish(self, slot: int, tok: int) -> bool:
        req = self.slots[slot]
        if tok == self.eos_id or len(req.out) >= req.max_new \
                or self.slot_pos[slot] >= self.max_len:
            req.done = True
            req.finish_t = time.perf_counter()
            self.completed.append(req)
            self.free_slot(slot)
            return True
        return False

    # -- single-queue request lifecycle ------------------------------------
    def submit(self, req: Request):
        req.submit_t = time.perf_counter()
        self.queue.append(req)

    def _admit_from_queue(self):
        while self.queue and self.can_admit(self.queue[0]):
            self.admit(self.queue.pop(0))
        if (self.paged and self.queue and self.n_active == 0
                and self.pager.pages_in_use == 0
                and not self.can_admit(self.queue[0])):
            # nothing running, nothing allocated, and the head request
            # still doesn't fit: it never will — surface the config error
            # instead of spinning forever in run().
            req = self.queue[0]
            raise paging.PagesExhausted(
                f"request uid={req.uid} needs "
                f"{self.pager.pages_for(len(req.prompt) + 1)} pages but the "
                f"pool only has {self.pages}")

    def step(self):
        """Admit what fits, then one decode step for all active slots."""
        self._admit_from_queue()
        return self.decode_once()

    def run(self, max_steps: int = 10_000):
        steps = 0
        while (self.queue or self.n_active) and steps < max_steps:
            self.step()
            steps += 1
        return self.completed
