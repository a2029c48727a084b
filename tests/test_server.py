"""The ServingRuntime control plane: declarative specs, heterogeneous
per-partition execution policies, live tenant migration, and the
report/fairness accounting fixes.

The migration contracts under test (the tentpole's acceptance criteria):

* token-for-token equality — a tenant migrated MID-REQUEST (its per-slot
  KV/SSM cache state handed off between partitions) produces exactly the
  tokens of the same tenant served solo;
* drain-under-load — a migration with no free target slot defers the
  handoff (the request keeps decoding at the source) and the source
  admits nothing new for the tenant;
* slot isolation — the handed-off slot is left fully cleared, so its
  next occupant cannot attend to the emigrant's KV rows;
* exact accounting — one global lockstep step domain: turnaround equals
  observed runtime steps even when a request crosses partitions, and the
  fused report folds the tenant's history once (no double counting).

Plus the satellite regressions: registered-but-idle and starved tenants
in fairness denominators, and the AdaptiveQuota occupancy signal.
"""
import argparse
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core import execution as ex
from repro.models import init_params
from repro.models.layers import RuntimeCfg
from repro.runtime import telemetry
from repro.runtime.scheduler import AdaptiveQuota, StreamScheduler
from repro.runtime.serve_loop import Request, ServeSession
from repro.runtime.server import (
    MigrationSpec, PartitionSpec, ServingRuntime, ServingSpec, TenantSpec,
    run_serving)

RT = RuntimeCfg(ssm_chunk=16)
MAX_LEN = 64
BF16 = "bf16:dense:jnp"
FP8SP = "fp8:sparse24:jnp"


@pytest.fixture(scope="module")
def model():
    cfg = get_reduced("llama3-8b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _requests(cfg, tenant_idx, n=2, max_new=6, length=5):
    rng = np.random.default_rng(tenant_idx)
    return [Request(uid=tenant_idx * 100 + j,
                    prompt=rng.integers(0, cfg.vocab_size, length)
                    .astype(np.int32), max_new=max_new)
            for j in range(n)]


def _runtime(model, spec, **kw):
    cfg, params = model
    return ServingRuntime(params, cfg, spec, rt=RT, **kw)


def _spec(n=2, policies=None, migration=None, slots=2, **kw):
    pols = policies or [None] * n
    return ServingSpec(
        partitions=tuple(PartitionSpec(policy=p) for p in pols),
        placement=kw.pop("placement", "load_aware"),
        batch_slots=slots, max_len=MAX_LEN,
        migration=migration or MigrationSpec(), **kw)


def _solo_outputs(model, requests, policy=None, slots=2):
    cfg, params = model
    sess = ServeSession(params, cfg, batch_slots=slots, max_len=MAX_LEN,
                        rt=RT,
                        policy=ex.parse_policy(policy) if policy else None)
    outs = []
    for req in requests:
        solo = Request(uid=req.uid, prompt=req.prompt.copy(),
                       max_new=req.max_new)
        sess.submit(solo)
        outs.append(solo)
    sess.run()
    return [r.out for r in outs]


# ---------------------------------------------------------------------------
# ServingSpec (declarative surface)
# ---------------------------------------------------------------------------

def test_spec_json_round_trip(tmp_path):
    spec = ServingSpec(
        partitions=(PartitionSpec(policy=FP8SP, quota="adaptive"),
                    PartitionSpec(admission="fifo", batch_slots=8)),
        placement="packed", batch_slots=4, max_len=96, temperature=0.5,
        seed=3, policy=BF16,
        migration=MigrationSpec(enabled=True, interval=5, threshold=3.0,
                                cooldown=7, max_migrations=2),
        tenants=(TenantSpec(id="a", weight=2.0, partition=1),
                 TenantSpec(id="b")))
    path = spec.save(str(tmp_path / "spec.json"))
    loaded = ServingSpec.load(path)
    assert loaded == spec
    # an ExecutionPolicy instance serializes through its full spec string
    pol = ex.ExecutionPolicy(precision="fp8", sparsity="sparse24",
                             backend="jnp", block_m=128, block_n=128,
                             block_k=256, streams=4)
    spec2 = ServingSpec(partitions=(PartitionSpec(policy=pol),))
    again = ServingSpec.from_json(spec2.to_json())
    assert again.partitions[0].policy == pol.full_spec()
    assert ex.parse_policy(again.partitions[0].policy) == pol


def test_spec_validation():
    with pytest.raises(ValueError):
        ServingSpec(partitions=())
    with pytest.raises(ValueError):
        ServingSpec(placement="nearest")
    with pytest.raises(ValueError):
        PartitionSpec(admission="lottery")
    with pytest.raises(ValueError):
        PartitionSpec(quota="lottery")
    with pytest.raises(ValueError):
        MigrationSpec(threshold=0.9)
    with pytest.raises(ValueError):
        MigrationSpec(interval=0)
    with pytest.raises(ValueError):            # duplicate tenant ids
        ServingSpec(tenants=(TenantSpec(id="a"), TenantSpec(id="a")))
    with pytest.raises(ValueError):            # pin outside the partitions
        ServingSpec(tenants=(TenantSpec(id="a", partition=1),))
    with pytest.raises(ValueError):            # unknown field
        ServingSpec.from_dict({"partitions": 1, "placment": "spread"})
    # int shorthand builds N default partitions
    assert ServingSpec.from_dict({"partitions": 3}).n_partitions == 3


def test_launch_serve_flags_build_spec(tmp_path):
    """The legacy flag cluster is shorthand for a spec (satellite)."""
    from repro.launch.serve import build_spec
    args = argparse.Namespace(
        partitions=2, placement="load_aware", adaptive_quota=True,
        admission="fair_quantum", slots=3, max_len=48, temperature=0.0,
        seed=1, migrate=True)
    spec = build_spec(args, "auto")
    assert spec.n_partitions == 2
    assert spec.partitions[0].quota == "adaptive"
    assert spec.migration.enabled and spec.placement == "load_aware"
    assert spec.batch_slots == 3 and spec.policy == "auto"
    assert ServingSpec.load(spec.save(str(tmp_path / "s.json"))) == spec


# ---------------------------------------------------------------------------
# Heterogeneous per-partition policies
# ---------------------------------------------------------------------------

def test_per_partition_policies_resolved_and_traced(model):
    """One runtime, two policies: the fp8/sparse24 partition and the bf16
    partition run side by side, sessions reflect their partition-local
    policy (not the ambient default), and the merged tracer's decode
    events carry both policy tags (acceptance criterion)."""
    cfg, _ = model
    rt = _runtime(model, _spec(policies=[BF16, FP8SP]))
    assert rt.sessions[0].cfg.precision == "bf16"
    assert rt.sessions[1].cfg.precision == "fp8"
    assert rt.sessions[1].cfg.sparsity_24
    assert rt.policy_key(0) == BF16 and rt.policy_key(1) == FP8SP
    rt.add_tenant("b", partition=0)
    rt.add_tenant("f", partition=1)
    for r in _requests(cfg, 0, n=1, max_new=4):
        rt.submit("b", r)
    for r in _requests(cfg, 1, n=1, max_new=4):
        rt.submit("f", r)
    rt.drain()
    pols = {(e.partition, e.policy)
            for e in rt.merged_tracer().events("decode")}
    assert (0, BF16) in pols and (1, FP8SP) in pols


def test_partition_local_policy_beats_ambient_default(model):
    """core/execution honors the policy scope over the module default:
    the redesign's resolution seam."""
    scoped = ex.ExecutionPolicy(precision="fp8", backend="jnp")
    ambient = ex.ExecutionPolicy(precision="bf16", backend="ref")
    ex.set_default_policy(ambient)
    try:
        assert ex.get_default_policy() == ambient
        with ex.policy_scope(scoped):
            assert ex.get_default_policy() == scoped
            assert ex.policy_from(model[0], RT) == scoped
            with ex.policy_scope(None):           # nested null scope
                assert ex.get_default_policy() == ambient
        assert ex.get_default_policy() == ambient
    finally:
        ex.set_default_policy(None)
    assert ex.get_scope_policy() is None


def test_partition_batch_slots_override(model):
    spec = ServingSpec(partitions=(PartitionSpec(batch_slots=1),
                                   PartitionSpec()),
                       batch_slots=3, max_len=MAX_LEN)
    rt = _runtime(model, spec)
    assert rt.sessions[0].batch_slots == 1
    assert rt.sessions[1].batch_slots == 3


# ---------------------------------------------------------------------------
# Live migration
# ---------------------------------------------------------------------------

def test_manual_migration_mid_request_token_equality(model):
    """THE core contract: a request whose KV/SSM cache state is handed
    off between partitions mid-stream finishes with exactly the tokens of
    the solo run, and turnaround accounting stays exact (one global step
    domain)."""
    cfg, _ = model
    rt = _runtime(model, _spec())
    rt.add_tenant("mover", partition=0)
    reqs = _requests(cfg, 0, n=2, max_new=10)
    for r in reqs:
        rt.submit("mover", r)
    for _ in range(3):
        rt.step()                      # both slots active, mid-request
    assert rt.sessions[0].n_active == 2
    rec = rt.migrate("mover", 1)
    assert rec.slots_handed_off == 2   # target had two free slots
    assert rec.done                    # queue empty + all slots moved
    assert rt.tenant_partition["mover"] == 1
    assert "mover" not in rt.schedulers[0].tenants
    steps = 3
    while not all(r.done for r in reqs):
        rt.step()
        steps += 1
        assert steps < 100
    assert [r.out for r in reqs] == _solo_outputs(model, reqs)
    # exact accounting: turnaround in the global lockstep domain
    for r in reqs:
        assert r.submit_step == 0 and r.finish_step - r.submit_step <= steps
        assert r.finish_step == 9      # admit step 0 emits token #1
    rep = rt.report()
    (row,) = rep.tenants
    assert row.submitted == 2 and row.completed == 2
    assert row.migrations == 1 and row.partition == 1
    assert rep.migrations == 1
    phases = [e.meta["phase"] for e in rt.merged_tracer().events("migrate")]
    assert phases.count("start") == 2      # recorded on both endpoints
    assert phases.count("handoff") == 4    # 2 slots x both endpoints
    assert phases.count("done") == 2


@pytest.mark.parametrize("paged", [False, True])
def test_device_partitions_pin_state_and_hand_off(model, paged):
    """Partitions bound to a device keep params and all serving state
    there, and a mid-request handoff puts the slot on the importer's
    device — still token-for-token equal to the solo run."""
    from repro.runtime.server import DevicePartition
    cfg, _ = model
    dev = jax.devices()[0]
    parts = [DevicePartition(i, (dev,)) for i in range(2)]
    rt = _runtime(model, _spec(paged=paged, page_size=8), partitions=parts)
    rt.add_tenant("mover", partition=0)
    reqs = _requests(cfg, 0, n=2, max_new=10)
    for r in reqs:
        rt.submit("mover", r)
    for _ in range(3):
        rt.step()
    assert rt.migrate("mover", 1).slots_handed_off == 2
    while not all(r.done for r in reqs):
        rt.step()
    assert [r.out for r in reqs] == _solo_outputs(model, reqs)
    for sess in rt.sessions:
        assert sess.device == dev
        for tree in (sess.params, sess.caches, sess.tokens, sess.rng):
            for leaf in jax.tree_util.tree_leaves(tree):
                assert leaf.devices() == {dev} and leaf.committed


def test_migration_drains_under_load(model):
    """With no free slot on the target, the handoff defers: the in-flight
    request keeps decoding on the (frozen) source and crosses over only
    when the target frees a slot; the source admits nothing new for the
    tenant after the freeze."""
    cfg, _ = model
    rt = _runtime(model, _spec())
    rt.add_tenant("blocker", partition=1)
    rt.add_tenant("mover", partition=0)
    for r in _requests(cfg, 9, n=2, max_new=12):
        rt.submit("blocker", r)        # fills both target slots
    mover_reqs = _requests(cfg, 0, n=2, max_new=16)
    for r in mover_reqs:
        rt.submit("mover", r)
    for _ in range(2):
        rt.step()
    rec = rt.migrate("mover", 1)
    assert not rec.done and rec.slots_handed_off == 0
    admitted_before = rt.schedulers[0].admitted_order.count("mover")
    rt.drain()
    assert rec.done and rec.slots_handed_off >= 1
    # freeze honored: the source admitted no mover request post-migration
    assert rt.schedulers[0].admitted_order.count("mover") == admitted_before
    assert [r.out for r in mover_reqs] == _solo_outputs(model, mover_reqs)
    rep = rt.report()
    row = {t.tenant_id: t for t in rep.tenants}["mover"]
    assert row.submitted == 2 and row.completed == 2 and row.migrations == 1


def test_handoff_slot_isolation(model):
    """The vacated source slot is bit-clean after a live handoff: pos
    rows read unwritten, k/v zeroed, and the next occupant reproduces its
    solo tokens exactly (cache-handoff slot-isolation)."""
    cfg, _ = model
    rt = _runtime(model, _spec(slots=1))
    rt.add_tenant("mover", partition=0)
    (req,) = _requests(cfg, 0, n=1, max_new=14)
    rt.submit("mover", req)
    for _ in range(3):
        rt.step()
    rt.migrate("mover", 1)
    caches = rt.sessions[0].caches
    assert (np.asarray(caches["layers"]["b0"]["pos"]) == -1).all()
    assert (np.asarray(caches["layers"]["b0"]["k"], np.float32) == 0).all()
    rt.add_tenant("fresh", partition=0)
    (fresh,) = _requests(cfg, 7, n=1, max_new=8)
    rt.submit("fresh", fresh)
    rt.drain()
    assert req.done and fresh.done
    assert [fresh.out] == _solo_outputs(model, [fresh], slots=1)
    assert [req.out] == _solo_outputs(model, [req], slots=1)


def test_live_handoff_requires_policy_compatible_partitions(model):
    """An in-flight request's arithmetic cannot change mid-stream: live
    migration across policy-incompatible partitions is refused, while a
    queued-only tenant migrates freely (it executes wholly under the
    target policy)."""
    cfg, _ = model
    rt = _runtime(model, _spec(policies=[BF16, FP8SP]))
    rt.add_tenant("t", partition=0)
    for r in _requests(cfg, 0, n=3, max_new=8):
        rt.submit("t", r)
    rt.step()
    with pytest.raises(ValueError, match="execution policies"):
        rt.migrate("t", 1)
    rt.drain()
    # queued-only: a fresh tenant with no active slots moves anywhere
    rt2 = _runtime(model, _spec(policies=[BF16, FP8SP]))
    rt2.add_tenant("q", partition=0)
    qreqs = _requests(cfg, 3, n=2, max_new=6)
    for r in qreqs:
        rt2.submit("q", r)
    rec = rt2.migrate("q", 1)          # nothing admitted yet
    assert rec.done and rec.queued_moved == 2
    rt2.drain()
    assert [r.out for r in qreqs] == _solo_outputs(model, qreqs,
                                                   policy=FP8SP)


def test_load_aware_auto_migration_on_skewed_load(model):
    """The re-route path fires on its own: a flooding tenant diverges its
    partition's load past the threshold, migrates to the idle partition
    (live handoff included), and the victims stay token-exact and fair
    (the fig19 headline at test scale)."""
    cfg, _ = model
    rt = _runtime(model, _spec(
        migration=MigrationSpec(enabled=True, interval=4, threshold=2.0,
                                cooldown=8)))
    rt.add_tenant("hog", partition=0)
    rt.add_tenant("victim", partition=0)
    hog_reqs = _requests(cfg, 0, n=6, max_new=8)
    for r in hog_reqs:
        rt.submit("hog", r)
    vic_reqs = _requests(cfg, 1, n=2, max_new=6)
    for r in vic_reqs:
        rt.submit("victim", r)
    rt.drain()
    assert rt.migrations and rt.migrations[0].done
    assert rt.migrations[0].reason == "load_aware"
    assert rt.tenant_partition["hog"] == 1     # flooder took the spare
    assert [r.out for r in hog_reqs] == _solo_outputs(model, hog_reqs)
    assert [r.out for r in vic_reqs] == _solo_outputs(model, vic_reqs)
    rep = rt.report()
    from repro.core.concurrency import fairness
    vic_ta = [t.mean_turnaround_steps for t in rep.tenants
              if t.tenant_id != "hog"]
    assert fairness(vic_ta) >= 0.8
    assert rep.migrations >= 1


def test_migration_disabled_means_static_routing(model):
    """The null hypothesis: with migration off, the same skew never
    re-routes anyone (PR 4 behavior preserved)."""
    cfg, _ = model
    rt = _runtime(model, _spec())
    rt.add_tenant("hog", partition=0)
    rt.add_tenant("victim", partition=0)
    for r in _requests(cfg, 0, n=4, max_new=6):
        rt.submit("hog", r)
    rt.drain()
    assert not rt.migrations
    assert rt.tenant_partition == {"hog": 0, "victim": 0}


# ---------------------------------------------------------------------------
# Report / fairness accounting (satellite regressions)
# ---------------------------------------------------------------------------

def test_registered_but_idle_tenant_appears_in_report(model):
    """A tenant that registered but never submitted must appear in the
    fused report rows and in the merged tracer's tenant enumeration
    instead of silently vanishing; tenants WITH demand keep their
    fairness index (no spurious zero from the idle tenant)."""
    cfg, _ = model
    rt = _runtime(model, _spec(n=1, slots=2))
    for tid in ("busy1", "busy2", "idle"):
        rt.add_tenant(tid)
    for i, tid in enumerate(("busy1", "busy2")):
        for r in _requests(cfg, i, n=1, max_new=4):
            rt.submit(tid, r)
    rt.drain()
    rep = rt.report()
    rows = {t.tenant_id: t for t in rep.tenants}
    assert set(rows) == {"busy1", "busy2", "idle"}
    assert rows["idle"].submitted == 0 and rows["idle"].completed == 0
    assert rep.n_tenants == 3
    assert rep.fairness >= 0.8         # over the two equal demand tenants
    merged = rt.merged_tracer()
    assert "idle" in merged.known_tenants()
    assert "idle: 0 req" in merged.summary()
    # scheduler-level registration is traced too
    assert merged.tenant_counts("register").get("idle") == 1


def test_starved_tenant_drags_fairness_down(model):
    """A tenant with demand that never completes must count against
    fairness via its elapsed wait (previously it vanished entirely and a
    starving scheduler looked perfectly fair). fifo is the starving
    policy: the first tenant's backlog holds the only slot."""
    cfg, _ = model
    spec = ServingSpec(partitions=(PartitionSpec(admission="fifo"),),
                       batch_slots=1, max_len=MAX_LEN)
    rt = _runtime(model, spec)
    rt.add_tenant("served")
    rt.add_tenant("starved")
    for r in _requests(cfg, 0, n=1, max_new=3):
        rt.submit("served", r)
    # a long request behind it keeps the single slot busy at the cutoff
    for r in _requests(cfg, 1, n=1, max_new=40):
        rt.submit("served", r)
    for r in _requests(cfg, 2, n=1, max_new=4):
        rt.submit("starved", r)
    rt.drain(max_steps=12)
    rep = rt.report()
    rows = {t.tenant_id: t for t in rep.tenants}
    assert rows["served"].completed >= 1
    assert rows["starved"].completed == 0 and rows["starved"].submitted == 1
    assert rep.fairness < 0.8, rep.summary()


# ---------------------------------------------------------------------------
# AdaptiveQuota occupancy signal (satellite)
# ---------------------------------------------------------------------------

def test_adaptive_quota_occupancy_signal(model):
    """Grid-fill collapse shrinks the aggregate slot budget (never below
    one slot per tenant) and recovery restores it — the ROADMAP 'fold the
    occupancy histogram into AdaptiveQuota' item."""
    cfg, params = model
    sess = ServeSession(params, cfg, batch_slots=4, max_len=MAX_LEN, rt=RT)
    tracer = telemetry.Tracer()
    aq = AdaptiveQuota(interval=2, fill_floor=0.5, n_cores=4)
    sched = StreamScheduler(sess, admission="fair_quantum", quota=aq,
                            tracer=tracer)
    sched.add_tenant("a")
    sched.add_tenant("b")
    assert sum(aq.slot_cap(sched, t) for t in sched.tenants.values()) == 4
    for _ in range(3):                       # collapsed fill: 1 tile / 4
        tracer.record_matmul(8, 8, 8, precision="bf16")
    for _ in range(3):
        sched.step()                         # interval hits at step 2
    assert aq.occupancy_shrinks == 1
    assert aq.budget(sched) == 3
    assert sum(aq.caps.values()) <= 3
    for _ in range(4):
        sched.step()                         # keeps collapsing to floor
    assert aq.budget(sched) == 2             # floor: one slot per tenant
    assert sum(aq.caps.values()) == 2
    events = [e for e in tracer.events("quota")
              if e.meta.get("signal") == "occupancy"]
    assert events and events[0].meta["fill"] < 0.5
    # recovery: saturate the window with high-fill GEMMs
    for _ in range(20):
        tracer.record_matmul(1024, 1024, 1024, precision="bf16")
    for _ in range(2):
        sched.step()
    assert aq.budget(sched) == 3             # one slot back per interval
    assert sum(aq.caps.values()) == 3        # caps REGROW with the budget
    for _ in range(2):
        sched.step()
    assert aq.budget(sched) == 4             # fully recovered
    assert sum(aq.caps.values()) == 4
    # defaults leave the signal off: no behavior change for existing users
    assert AdaptiveQuota().fill_floor is None


# ---------------------------------------------------------------------------
# Deprecated facades
# ---------------------------------------------------------------------------

def test_partitioned_server_shim_warns_and_serves(model):
    cfg, params = model
    from repro.runtime.partition import PartitionedServer, run_partitioned
    with pytest.warns(DeprecationWarning, match="ServingRuntime"):
        srv = PartitionedServer(params, cfg, n_partitions=2,
                                batch_slots=2, max_len=MAX_LEN, rt=RT,
                                placement="spread")
    srv.add_tenant("t0")
    srv.add_tenant("t1")
    reqs = _requests(cfg, 0, n=2, max_new=4)
    for i, r in enumerate(reqs):
        srv.submit(f"t{i % 2}", r)
    done = srv.run()                   # legacy verb -> drain
    assert len(done) == 2
    rep = srv.report()
    assert rep.n_partitions == 2 and rep.tokens_out == 8
    assert isinstance(srv.runtime, ServingRuntime)
    with pytest.warns(DeprecationWarning):
        run_partitioned(params, cfg,
                        {"t": _requests(cfg, 1, n=1, max_new=4)},
                        n_partitions=1, batch_slots=2, max_len=MAX_LEN,
                        rt=RT)


def test_run_serving_with_declared_tenants(model):
    """Spec-declared tenants are pre-registered (pinned or routed) and
    extra workload tenants are routed on demand."""
    cfg, params = model
    spec = dataclasses.replace(
        _spec(n=2, placement="spread"),
        tenants=(TenantSpec(id="pinned", partition=1),))
    rep = run_serving(params, cfg, spec,
                      {"pinned": _requests(cfg, 0, n=1, max_new=4),
                       "routed": _requests(cfg, 1, n=1, max_new=4)},
                      rt=RT)
    assert rep.tenant_partition["pinned"] == 1
    assert rep.tenant_partition["routed"] == 0   # spread fills the gap
    assert rep.tokens_out == 8


# ---------------------------------------------------------------------------
# Migration hysteresis (cooldown + strict-improvement victim selection)
# ---------------------------------------------------------------------------

def test_migration_cooldown_blocks_ping_pong(model):
    """Oscillating load must not cause migration ping-pong: after the
    first re-route, an immediate skew inversion stays put until the
    cooldown expires, and consecutive migrations are always at least
    ``cooldown`` steps apart."""
    cfg, _ = model
    cool = 12
    rt = _runtime(model, _spec(migration=MigrationSpec(
        enabled=True, interval=2, threshold=2.0, cooldown=cool)))
    rt.add_tenant("hog", partition=0)
    rt.add_tenant("small", partition=0)
    rt.add_tenant("b", partition=1)
    for r in _requests(cfg, 0, n=6, max_new=8):
        rt.submit("hog", r)
    for r in _requests(cfg, 1, n=2, max_new=6):
        rt.submit("small", r)
    steps = 0
    while not rt.migrations and steps < 60:
        rt.step()
        steps += 1
    assert rt.migrations and rt.migrations[0].reason == "load_aware"
    first = rt.migrations[0].start_step
    # oscillation stimulus: invert the skew right away — flood the
    # partition the hog just landed on
    assert rt.tenant_partition["hog"] == 1
    for r in _requests(cfg, 5, n=8, max_new=24):
        rt.submit("b", r)
    guard = 0
    while rt.step_count + 1 < first + cool and guard < 200:
        rt.step()
        guard += 1
        assert len(rt.migrations) == 1   # hysteresis: no ping-pong yet
    rt.drain()
    starts = [m.start_step for m in rt.migrations]
    assert all(b - a >= cool for a, b in zip(starts, starts[1:]))


def test_pick_victim_requires_strict_improvement(model):
    """The victim picker is the other half of the hysteresis: a move
    that merely mirrors the imbalance (or ties it) is refused, and when
    several tenants would help, the best equalizer wins."""
    cfg, _ = model
    rt = _runtime(model, _spec(placement="spread"))
    rt.add_tenant("solo", partition=0)
    rt.add_tenant("peer", partition=1)
    # queued-only work with exact costs: request_cost = len(prompt)+max_new
    (r,) = _requests(cfg, 0, n=1, max_new=11, length=5)      # cost 16
    rt.submit("solo", r)
    works = [rt._partition_work(0), rt._partition_work(1)]
    assert works == [16.0, 0.0]
    # a lone tenant's move mirrors the whole imbalance onto the target:
    # |0 - 16| == |16 - 0| -> not a strict improvement -> no victim
    assert rt._pick_victim(0, 1, works) is None
    # a smaller second tenant and some target-side work break the tie:
    # moving "lite" (cost 8) equalizes 26/8 -> 18/16; moving "solo"
    # (cost 18) overshoots to 8/26 (no better than now) and is refused
    rt.add_tenant("lite", partition=0)
    (r2,) = _requests(cfg, 1, n=1, max_new=13, length=5)     # cost 18
    rt.submit("solo", r2)
    rt.schedulers[0].tenants["solo"].queue.remove(r)
    rt.submit("lite", _requests(cfg, 2, n=1, max_new=3, length=5)[0])
    rt.submit("peer", _requests(cfg, 3, n=1, max_new=3, length=5)[0])
    works = [rt._partition_work(0), rt._partition_work(1)]
    assert works == [26.0, 8.0]
    assert rt._pick_victim(0, 1, works) == "lite"


# ---------------------------------------------------------------------------
# Async execution lanes (overlap on/off equivalence)
# ---------------------------------------------------------------------------

def test_overlap_serving_token_equality_and_lane_events(model):
    """The tentpole contract: stepping heterogeneous partitions through
    execution lanes (planner-paired sparse24 beside dense) changes wall
    time only — greedy tokens match the serialized loop and the solo
    runs, and the overlap decision is visible on the decode events."""
    cfg, _ = model
    outs = {}
    for name, ov in (("overlap", True), ("serialized", False)):
        reqs = _requests(cfg, 0, n=6, max_new=6)
        rt = _runtime(model, _spec(policies=[FP8SP, BF16],
                                   placement="spread", overlap=ov))
        rt.add_tenant("t0")
        rt.add_tenant("t1")
        for j, r in enumerate(reqs):
            rt.submit(f"t{j % 2}", r)
        rt.drain()
        outs[name] = [list(r.out) for r in reqs]
        assert all(r.done for r in reqs)
        if ov:
            merged = rt.merged_tracer()
            evs = [e for e in merged.events("decode")
                   if e.lane and e.overlap_group >= 0]
            assert evs, "overlap on but no lane-tagged decode events"
            assert {e.lane for e in evs} == {"lane0", "lane1"}
            assert merged.overlap_summary()["groups"] >= 1
            solo = {}
        else:
            evs = [e for e in rt.merged_tracer().events("decode")
                   if e.lane.startswith("lane") or e.overlap_group >= 0]
            assert not evs, \
                "serialized loop must not run on planner lanes"
    assert outs["overlap"] == outs["serialized"]
    # per-tenant solo equality under each partition's own policy
    reqs = _requests(cfg, 0, n=6, max_new=6)
    for pol, k in ((FP8SP, 0), (BF16, 1)):
        mine = [r for j, r in enumerate(reqs) if j % 2 == k]
        assert [out for j, out in enumerate(outs["overlap"])
                if j % 2 == k] == _solo_outputs(model, mine, policy=pol)


def test_overlap_token_equality_across_live_migration(model):
    """Lanes stay token-exact through a mid-request live handoff."""
    cfg, _ = model
    outs = {}
    for ov in (True, False):
        reqs = _requests(cfg, 0, n=2, max_new=10)
        rt = _runtime(model, _spec(overlap=ov))
        rt.add_tenant("mover", partition=0)
        for r in reqs:
            rt.submit("mover", r)
        for _ in range(3):
            rt.step()
        rt.migrate("mover", 1)
        rt.drain()
        assert all(r.done for r in reqs)
        outs[ov] = [list(r.out) for r in reqs]
        assert outs[ov] == [list(o) for o in _solo_outputs(model, reqs)]
    assert outs[True] == outs[False]
