"""Serving-fleet contracts (CPU-deterministic, tier-1).

The fleet's correctness story extends the engine's token-identity
invariant across failures: whatever the supervisor does — replica
crash, sick-replica drain, slot-leak re-form, migration onto survivors
— every request that the fleet accepted and finished must equal the
one-shot full-forward ``generate`` for its prompt, with zero lost and
zero duplicated tokens.  The robustness story is explicit degradation:
every request turned away is counted with a reason and a Retry-After
hint, never silently dropped.  Chaos is scripted through the seeded
``FaultPlan`` fleet vocabulary so each scenario replays exactly.
"""

import json

import numpy as np
import pytest

import jax

from skycomputing_tpu.builder import build_layer_stack
from skycomputing_tpu.dynamics import (
    FaultInjectionHook,
    FaultPlan,
    FleetFaultInjector,
    WorkerManager,
)
from skycomputing_tpu.fleet import (
    AdmissionController,
    FleetSupervisor,
    Router,
    ServingFleet,
)
from skycomputing_tpu.fleet.admission import (
    DEADLINE_UNMEETABLE,
    NO_HEALTHY_REPLICA,
    QUEUE_FULL,
    SHED_LOW_PRIORITY,
)
from skycomputing_tpu.fleet.replica import DRAINING, HEALTHY, RETIRED
from skycomputing_tpu.models.gpt import (
    GptConfig,
    generate,
    gpt_layer_configs,
)
from skycomputing_tpu.serving import (
    QueueFullError,
    Request,
    ServingEngine,
)

pytestmark = pytest.mark.fleet


@pytest.fixture(scope="module")
def gpt():
    """Tiny GPT + host params + jitted one-shot forward reference
    (the test_serving fixture, shared by every fleet scenario)."""
    cfg = GptConfig(vocab_size=512, hidden_size=64, num_hidden_layers=2,
                    num_attention_heads=2, max_position_embeddings=64,
                    dropout_prob=0.0, dtype="float32")
    layer_cfgs = gpt_layer_configs(cfg, deterministic=True)
    stack = build_layer_stack(layer_cfgs)
    params = stack.init(jax.random.key(7), np.ones((1, 5), np.int32))
    fwd = jax.jit(lambda ids: stack.apply(params, ids))
    return layer_cfgs, params, fwd


def reference(fwd, request):
    out = generate(fwd, request.prompt[None],
                   max_new_tokens=request.max_new_tokens,
                   context_length=64)
    return out[0]


def mixed_requests(rng, specs):
    return [
        Request(prompt=rng.integers(1, 512, (l,)).astype(np.int32),
                max_new_tokens=n)
        for l, n in specs
    ]


def fast_supervisor(**kw):
    """Supervisor tuned for seconds-scale tests: detect every tick, one
    missed beat is death.  The latency probe is OFF unless a test asks
    for it: it compares wall-clock tick times, and on a loaded machine
    one tick is easily three times another, so a test that injects no
    latency fault would see a spurious SICK verdict and re-form."""
    defaults = dict(check_every=1, heartbeat_misses=1, grace_ticks=2,
                    baseline_ticks=3, k_checks=2,
                    sick_threshold=float("inf"))
    defaults.update(kw)
    return FleetSupervisor(**defaults)


def assert_identity(fwd, requests, outputs):
    """Zero lost, zero duplicated tokens: byte-exact vs one-shot."""
    for r in requests:
        np.testing.assert_array_equal(
            outputs[r.request_id], reference(fwd, r)
        )


# --------------------------------------------------------------------------
# router decision logic (pure, synthetic snapshots)
# --------------------------------------------------------------------------


def snap(name, healthy=True, slots=4, free=4, depth=0, tpot=None):
    return dict(name=name, healthy=healthy, slots=slots,
                free_slots=free, queue_depth=depth, tpot_p95_s=tpot)


def test_router_least_loaded_under_skew():
    router = Router()
    snaps = [
        snap("a", depth=5, free=0),   # deeply backed up
        snap("b", depth=0, free=2),   # 2 occupied
        snap("c", depth=0, free=4),   # idle
    ]
    assert router.choose(snaps) == "c"
    # outstanding work counts occupied slots, not just queue depth
    assert router.rank(snaps) == ["c", "b", "a"]
    # a slow replica (high TPOT) is more loaded at equal depth
    snaps = [snap("a", free=0, tpot=0.5), snap("b", free=0, tpot=0.01)]
    assert router.choose(snaps) == "b"
    # only healthy replicas participate; none healthy -> no target
    snaps = [snap("a", healthy=False), snap("b")]
    assert router.rank(snaps) == ["b"]
    assert router.choose([snap("a", healthy=False)]) is None


def test_router_prefix_affinity_with_slack():
    router = Router(affinity_slack=2.0)
    prompt = list(range(1, 12))
    snaps = [snap("a"), snap("b")]
    assert router.choose(snaps, prompt) == "a"  # name tie-break
    router.record_dispatch("b", prompt)
    # sticky while b's load is within slack of the best...
    snaps = [snap("a"), snap("b", free=2)]  # b load 2, a load 0
    assert router.choose(snaps, prompt) == "b"
    # ...but never onto an overloaded replica
    snaps = [snap("a"), snap("b", free=0, depth=3)]
    assert router.choose(snaps, prompt) == "a"
    # a different prefix has no affinity
    assert router.choose([snap("a"), snap("b", free=2)],
                         list(range(50, 60))) == "a"
    # death forgets the affinity
    assert router.forget_replica("b") == 1
    assert router.choose([snap("a"), snap("b", free=2)], prompt) == "a"


# --------------------------------------------------------------------------
# admission decision logic (pure, synthetic state)
# --------------------------------------------------------------------------


def test_admission_bounds_priorities_and_deadlines():
    adm = AdmissionController(max_pending=8, shed_fraction=0.5,
                              service_s_estimate=0.1)
    ok = adm.decide(pending=0, capacity_slots=4)
    assert ok.admitted
    # full queue rejects with a positive, pending-monotone hint
    full = adm.decide(pending=8, capacity_slots=4)
    fuller = adm.decide(pending=16, capacity_slots=4)
    assert not full.admitted and full.reason == QUEUE_FULL
    assert full.retry_after_s > 0
    assert fuller.retry_after_s > full.retry_after_s
    # the shed band: batch sheds, interactive still admits
    shed = adm.decide(pending=5, capacity_slots=4, priority="batch")
    keep = adm.decide(pending=5, capacity_slots=4,
                      priority="interactive")
    assert not shed.admitted and shed.reason == SHED_LOW_PRIORITY
    assert shed.retry_after_s > 0
    assert keep.admitted
    # deadline-aware: an unmeetable deadline is rejected up front
    # (pending 3 sits below the shed band, so the deadline gate decides)
    late = adm.decide(pending=3, capacity_slots=1, deadline_s=0.05)
    assert not late.admitted and late.reason == DEADLINE_UNMEETABLE
    assert adm.decide(pending=3, capacity_slots=1,
                      deadline_s=10.0).admitted
    # dead fleet: nothing admits
    dead = adm.decide(pending=0, capacity_slots=0)
    assert not dead.admitted and dead.reason == NO_HEALTHY_REPLICA
    with pytest.raises(ValueError, match="priority"):
        adm.decide(pending=0, capacity_slots=4, priority="vip")
    # default bound scales with live capacity (tightens as replicas die)
    auto = AdmissionController(queue_factor=2.0)
    assert auto.pending_bound(8) == 16 and auto.pending_bound(4) == 8


# --------------------------------------------------------------------------
# bounded single-engine admission queue (the satellite)
# --------------------------------------------------------------------------


def test_engine_bounded_queue_reject_policy(gpt):
    layer_cfgs, params, _ = gpt
    engine = ServingEngine(layer_cfgs, params, num_slots=1, max_len=64,
                           buckets=(8,), max_queue=2)
    rng = np.random.default_rng(0)
    a, b, c = mixed_requests(rng, [(4, 3)] * 3)
    engine.submit(a)
    engine.submit(b)
    with pytest.raises(QueueFullError) as exc_info:
        engine.submit(c)
    assert exc_info.value.queue_depth == 2
    assert engine.stats.queue_rejections == 1
    assert engine.stats.snapshot()["queue_rejections"] == 1
    # the rejected request's state was never mutated
    assert c.status == "queued" and c.submitted_s is None


def test_engine_bounded_queue_shed_policy(gpt):
    layer_cfgs, params, _ = gpt
    engine = ServingEngine(layer_cfgs, params, num_slots=1, max_len=64,
                           buckets=(8,), max_queue=2,
                           queue_policy="shed")
    rng = np.random.default_rng(1)
    a, b, c = mixed_requests(rng, [(4, 3)] * 3)
    engine.submit(a)
    engine.submit(b)
    engine.submit(c)  # sheds the oldest (a), admits c
    assert a.status == "rejected"
    assert engine.stats.queue_rejections == 1
    assert [r.request_id for r in engine.queued_requests] == [
        b.request_id, c.request_id
    ]
    with pytest.raises(ValueError, match="queue_policy"):
        ServingEngine(layer_cfgs, params, num_slots=1, max_len=64,
                      buckets=(8,), queue_policy="drop")


def test_shed_never_drops_committed_tokens(gpt):
    """Shed victims are token-less only: a preempted (force-requeued)
    request with committed tokens is never shed — when nothing is
    sheddable, the policy degrades to reject, and an over-bound queue
    (force re-queues) sheds as many token-less victims as needed
    without raising."""
    layer_cfgs, params, fwd = gpt
    engine = ServingEngine(layer_cfgs, params, num_slots=1, max_len=64,
                           buckets=(8, 16), max_queue=1,
                           queue_policy="shed")
    rng = np.random.default_rng(10)
    resume_a, fresh, newcomer, last = mixed_requests(
        rng, [(5, 8), (4, 3), (3, 3), (3, 2)]
    )
    # a request mid-decode, preempted -> fills the queue with a
    # committed-token resume (force past the bound)
    engine.submit(resume_a)
    engine.step()
    engine.preempt(resume_a.request_id)
    assert engine.stats.queue_depth == 1
    # nothing sheddable (the resume has tokens): shed degrades to
    # reject instead of discarding the stream or raising mid-shed
    with pytest.raises(QueueFullError):
        engine.submit(newcomer)
    assert engine.stats.queue_rejections == 1
    assert resume_a.tokens  # stream intact
    # drain the resumes, then overfill with token-less requests via
    # preempt interleaving: shed clears as many as needed, no raise
    engine.run()
    np.testing.assert_array_equal(resume_a.output(),
                                  reference(fwd, resume_a))
    engine.submit(fresh)
    engine.submit(last)  # sheds `fresh` (token-less), admits
    assert fresh.status == "rejected"
    assert engine.stats.queue_rejections == 2


def test_preemption_bypasses_queue_bound(gpt):
    """The bound gates NEW admissions only: a preempted (already
    admitted) request always re-queues — shedding it would lose its
    committed tokens."""
    layer_cfgs, params, fwd = gpt
    engine = ServingEngine(layer_cfgs, params, num_slots=1, max_len=64,
                           buckets=(8, 16), max_queue=1)
    rng = np.random.default_rng(2)
    victim, waiter = mixed_requests(rng, [(5, 8), (4, 4)])
    engine.submit(victim)
    engine.step()  # victim takes the slot
    engine.submit(waiter)  # fills the bounded queue
    engine.preempt(victim.request_id)  # queue full -> force path
    assert engine.stats.queue_depth == 2
    assert engine.stats.queue_rejections == 0
    engine.run()
    np.testing.assert_array_equal(victim.output(),
                                  reference(fwd, victim))
    np.testing.assert_array_equal(waiter.output(),
                                  reference(fwd, waiter))


def test_engine_drain_migrates_streams_intact(gpt):
    """``drain()`` is the migration primitive: mid-decode eviction off
    one engine, resume on a DIFFERENT engine, streams byte-identical."""
    layer_cfgs, params, fwd = gpt
    devices = jax.devices()
    src = ServingEngine(layer_cfgs, params, num_slots=2, max_len=64,
                        buckets=(8, 16), devices=[devices[0]])
    dst = ServingEngine(layer_cfgs, params, num_slots=2, max_len=64,
                        buckets=(8, 16), devices=[devices[1]])
    rng = np.random.default_rng(3)
    requests = mixed_requests(rng, [(5, 9), (3, 6), (7, 8)])
    for r in requests:
        src.submit(r)
    for _ in range(3):
        src.step()  # all mid-flight on src
    moved = src.drain()
    assert len(moved) == 3 and not src.has_work()
    assert all(r.slot is None for r in moved)
    for r in moved:
        dst.submit(r)
    dst.run()
    for r in requests:
        np.testing.assert_array_equal(r.output(), reference(fwd, r))


# --------------------------------------------------------------------------
# fleet end-to-end
# --------------------------------------------------------------------------


def test_fleet_routes_and_serves_token_identical(gpt, devices):
    layer_cfgs, params, fwd = gpt
    fleet = ServingFleet(
        layer_cfgs, params, replicas=2,
        engine_kwargs=dict(num_slots=2, max_len=64, buckets=(8, 16)),
        supervisor=fast_supervisor(),
        devices=devices,
    )
    rng = np.random.default_rng(4)
    requests = mixed_requests(
        rng, [(5, 9), (3, 4), (12, 7), (7, 5), (16, 6), (2, 8)]
    )
    decisions = [fleet.submit(r) for r in requests]
    assert all(d.admitted and d.replica for d in decisions)
    # least-loaded routing spread the work over both replicas
    assert len({d.replica for d in decisions}) == 2
    outputs = fleet.run()
    assert_identity(fwd, requests, outputs)
    snap = fleet.metrics.snapshot()
    assert snap["fleet"]["dispatched"] == 6
    assert snap["fleet"]["failed"] == 0
    assert snap["fleet"]["ttft_p95_s"] > 0
    assert "replica0" in snap and "replica1" in snap


def test_fleet_prefix_affinity_yields_real_cache_hits(gpt, devices):
    """With PAGED replicas and the router's affinity key aligned to the
    radix sharing unit (``Router(page_size=...)``), same-system-prompt
    requests stick to one replica and the stickiness pays off as REAL
    ``prefix_hits`` there — the locality hint became cache locality."""
    layer_cfgs, params, fwd = gpt
    page_size = 8
    fleet = ServingFleet(
        layer_cfgs, params, replicas=2,
        engine_kwargs=dict(num_slots=2, max_len=48, buckets=(8, 16, 32),
                           kv_layout="paged", page_size=page_size,
                           max_concurrency=6),
        router=Router(page_size=page_size, affinity_slack=8.0),
        supervisor=fast_supervisor(),
        devices=devices,
    )
    rng = np.random.default_rng(23)
    # two distinct system prompts, each >= one full page so the radix
    # cache can share them; 3 requests per group, interleaved arrivals
    groups = [
        rng.integers(1, 512, (18,)).astype(np.int32) for _ in range(2)
    ]
    requests, placements = [], {0: set(), 1: set()}
    for wave in range(3):
        for gi, system in enumerate(groups):
            tail = rng.integers(1, 512, (3,)).astype(np.int32)
            r = Request(prompt=np.concatenate([system, tail]),
                        max_new_tokens=4)
            decision = fleet.submit(r)
            assert decision.admitted
            placements[gi].add(decision.replica)
            requests.append(r)
            fleet.run()  # drain so affinity, not load, decides routing
    # affinity held: each group landed on ONE replica every time
    assert all(len(p) == 1 for p in placements.values()), placements
    for r in requests:
        np.testing.assert_array_equal(r.output(), reference(fwd, r))
    # and the stickiness produced real prefix-cache hits: every request
    # after each group's first shares that group's system prompt
    snap = fleet.metrics.snapshot()
    hits = sum(
        snap[name]["prefix_hits"] for name in ("replica0", "replica1")
    )
    reused = sum(
        snap[name]["prefix_tokens_reused"]
        for name in ("replica0", "replica1")
    )
    assert hits >= 4, snap  # 2 groups x (3 - 1) followers
    assert reused >= 4 * 18  # at least the full system prompt each hit


def test_fleet_replica_kill_zero_lost_tokens(gpt, devices):
    """The headline chaos contract: kill a replica mid-run; its
    in-flight requests migrate recomputation-style onto survivors and
    every accepted request finishes token-identical — zero lost, zero
    duplicated tokens — while the dead replica re-forms."""
    from skycomputing_tpu import telemetry

    layer_cfgs, params, fwd = gpt
    plan = FaultPlan(
        [dict(iter=6, kind="replica_crash", replica=0)], seed=0
    )
    fleet = ServingFleet(
        layer_cfgs, params, replicas=3,
        engine_kwargs=dict(num_slots=2, max_len=64, buckets=(8, 16)),
        supervisor=fast_supervisor(),
        fault_injector=FleetFaultInjector(plan),
        devices=devices,
    )
    rng = np.random.default_rng(5)
    requests = mixed_requests(
        rng,
        [(5, 9), (3, 6), (12, 7), (7, 5), (16, 6), (2, 11), (6, 8),
         (9, 4)],
    )
    telemetry.enable_tracing()
    try:
        outputs = fleet.run(requests)
    finally:
        tracer = telemetry.get_tracer()
        events = tracer.to_chrome()["traceEvents"] if tracer else []
        telemetry.disable_tracing()
    assert len(outputs) == len(requests)
    assert_identity(fwd, requests, outputs)
    assert fleet.stats.failed == 0
    assert fleet.stats.migrations > 0
    assert fleet.stats.reforms == 1
    assert fleet.replicas[0].generation == 1
    assert fleet.replicas[0].state == HEALTHY
    kinds = [e["kind"] for e in fleet.supervisor.events]
    assert kinds[:3] == ["detect", "drain", "migrate"]
    assert "reformed" in kinds
    # the whole arc is visible on the fleet trace lane
    arcs = [e for e in events if e.get("name") == "fleet_heal"]
    assert {e["ph"] for e in arcs} == {"b", "e"}
    ends = [e for e in arcs if e["ph"] == "e"]
    assert ends[-1]["args"]["outcome"] == "reformed"
    spans = {e["name"] for e in events if e["ph"] == "X"}
    assert {"fleet.drain", "fleet.migrate", "fleet.reform"} <= spans


def test_fleet_sick_replica_drains_to_survivors(gpt, devices):
    """A latency-spiked replica is detected by the EWMA health score,
    drained through the preempt contract, and re-formed; requests that
    cannot re-bucket finish on the DRAINING replica — nothing fails."""
    layer_cfgs, params, fwd = gpt
    plan = FaultPlan(
        [dict(iter=8, kind="latency_spike", replica=1, seconds=0.05)],
        seed=0,
    )
    fleet = ServingFleet(
        layer_cfgs, params, replicas=2,
        engine_kwargs=dict(num_slots=2, max_len=64, buckets=(8, 16)),
        supervisor=fast_supervisor(sick_threshold=3.0),
        fault_injector=FleetFaultInjector(plan),
        devices=devices,
    )
    rng = np.random.default_rng(6)
    requests = mixed_requests(
        rng, [(5, 20), (3, 18), (12, 16), (7, 15), (6, 14), (9, 12)]
    )
    outputs = fleet.run(requests)
    assert len(outputs) == len(requests)
    assert_identity(fwd, requests, outputs)
    assert fleet.stats.failed == 0
    detects = [e for e in fleet.supervisor.events
               if e["kind"] == "detect"]
    assert detects and detects[0]["reason"] == "latency"
    assert detects[0]["score"] >= 3.0
    assert fleet.stats.reforms >= 1
    assert all(r.state == HEALTHY for r in fleet.replicas)


def test_fleet_slot_leak_detected_and_reformed(gpt, devices):
    layer_cfgs, params, fwd = gpt
    plan = FaultPlan(
        [dict(iter=4, kind="slot_leak", replica=0, count=2)], seed=0
    )
    fleet = ServingFleet(
        layer_cfgs, params, replicas=2,
        engine_kwargs=dict(num_slots=2, max_len=64, buckets=(8,),
                           max_concurrency=2),
        supervisor=fast_supervisor(),
        fault_injector=FleetFaultInjector(plan),
        devices=devices,
    )
    rng = np.random.default_rng(7)
    requests = mixed_requests(rng, [(4, 12), (5, 10), (3, 14), (6, 9)])
    outputs = fleet.run(requests)
    assert_identity(fwd, requests, outputs)
    reasons = [e["reason"] for e in fleet.supervisor.events
               if e["kind"] == "detect"]
    assert "slot_leak" in reasons
    assert fleet.stats.reforms >= 1
    # the re-formed replica's pool is whole again
    rep = fleet.replicas[0]
    assert rep.generation >= 1 and rep.slot_accounting_ok
    assert rep.engine.stages[0].pool.free_slots == 2


def test_fleet_reform_rollback_on_infeasible_reallocation(gpt, devices):
    """A re-form whose serving pre-flight rejects (the re-allocation no
    longer fits its budgets) rolls back structurally: no half-built
    replica, the fleet keeps serving on survivors, the failure is
    counted and the replica retires when its budget exhausts."""
    layer_cfgs, params, fwd = gpt
    wm = WorkerManager()
    wm.load_worker_pool_from_config([
        dict(name="n0", device_config=dict(device_index=0),
             extra_config=dict(mem_limit=10_000.0))
    ])
    worker = wm.worker_pool[0]
    worker.model_config = layer_cfgs
    worker.order = worker.rank + 1
    fleet = ServingFleet(
        layer_cfgs, params,
        replica_specs=[
            dict(worker_manager=wm, devices=[devices[0]]),
            dict(devices=[devices[1]]),
        ],
        engine_kwargs=dict(num_slots=2, max_len=64, buckets=(8, 16)),
        supervisor=fast_supervisor(max_reforms=1),
        fault_injector=FleetFaultInjector(FaultPlan(
            [dict(iter=3, kind="replica_crash", replica=0)], seed=0
        )),
    )
    # the world changed AFTER replica0 was built: its budget no longer
    # fits the slabs, so the re-form's verify-then-apply must reject
    worker.extra_config["mem_limit"] = 0.05
    rng = np.random.default_rng(8)
    requests = mixed_requests(
        rng, [(5, 9), (3, 7), (12, 8), (7, 6), (6, 9), (9, 5)]
    )
    outputs = fleet.run(requests)
    assert len(outputs) == len(requests)
    assert_identity(fwd, requests, outputs)
    assert fleet.stats.reform_failures == 1
    assert fleet.stats.reforms == 0
    assert fleet.replicas[0].state == RETIRED
    assert fleet.replicas[1].state == HEALTHY
    failed = [e for e in fleet.supervisor.events
              if e["kind"] == "reform_failed"]
    assert failed and "pre-flight" in failed[0]["error"]


def test_fleet_shed_under_overload_is_counted_never_silent(gpt, devices):
    """A 2x admission spike against a bounded fleet: the overflow is
    rejected with reasons and Retry-After hints, interactive traffic
    outlives batch traffic, and every ACCEPTED request still finishes
    token-identical."""
    layer_cfgs, params, fwd = gpt
    fleet = ServingFleet(
        layer_cfgs, params, replicas=2,
        engine_kwargs=dict(num_slots=2, max_len=64, buckets=(8,)),
        admission=AdmissionController(max_pending=4, shed_fraction=0.5),
        supervisor=fast_supervisor(),
        devices=devices,
    )
    rng = np.random.default_rng(9)
    batch = mixed_requests(rng, [(4, 6)] * 8)
    interactive = mixed_requests(rng, [(5, 5)] * 2)
    decisions = [fleet.submit(r) for r in batch]
    keep = [fleet.submit(r, priority="interactive")
            for r in interactive]
    rejected = [d for d in decisions + keep if not d.admitted]
    accepted = [r for r, d in
                zip(batch + interactive, decisions + keep)
                if d.admitted]
    assert rejected, "the spike must shed"
    assert all(d.reason and d.retry_after_s > 0 for d in rejected)
    # interactive is admitted past the shed band (pending < hard bound)
    assert sum(d.admitted for d in keep) > 0
    assert fleet.stats.rejected == len(rejected)
    assert sum(fleet.stats.rejected_by_reason.values()) == len(rejected)
    outputs = fleet.run()
    assert len(outputs) == len(accepted)
    assert_identity(fwd, accepted, outputs)
    # shed requests are terminally marked, not limbo'd
    for r, d in zip(batch + interactive, decisions + keep):
        if not d.admitted:
            assert r.status == "rejected"


# --------------------------------------------------------------------------
# fleet observability plane (request tracing, exporter, SLO monitor)
# --------------------------------------------------------------------------


def test_migrated_request_trace_single_id_no_orphans(gpt, devices):
    """One request id threads the whole waterfall across a replica
    kill: segments on the dead replica, a migrate marker, segments on
    the survivor — complete, ordered, zero orphaned spans."""
    from skycomputing_tpu import telemetry
    from skycomputing_tpu.telemetry.analysis import (
        request_ids,
        request_timeline,
    )

    layer_cfgs, params, fwd = gpt
    plan = FaultPlan(
        [dict(iter=6, kind="replica_crash", replica=0)], seed=0
    )
    fleet = ServingFleet(
        layer_cfgs, params, replicas=3,
        engine_kwargs=dict(num_slots=2, max_len=64, buckets=(8, 16)),
        supervisor=fast_supervisor(),
        fault_injector=FleetFaultInjector(plan),
        devices=devices,
    )
    rng = np.random.default_rng(5)
    requests = mixed_requests(
        rng,
        [(5, 9), (3, 6), (12, 7), (7, 5), (16, 6), (2, 11), (6, 8),
         (9, 4)],
    )
    tracer = telemetry.enable_tracing()
    try:
        outputs = fleet.run(requests)
        events = tracer.to_chrome()["traceEvents"]
    finally:
        telemetry.disable_tracing()
    assert_identity(fwd, requests, outputs)
    assert fleet.stats.migrations > 0

    migrated = []
    for rid in request_ids(events):
        timeline = request_timeline(events, rid)
        # EVERY request's trace is complete with no orphaned spans
        assert timeline["complete"], f"request {rid} has no terminal"
        assert timeline["orphan_spans"] == 0
        for a, b in zip(timeline["segments"],
                        timeline["segments"][1:]):
            assert b["start_ms"] >= a["start_ms"]
        if timeline["migrations"] >= 1:
            migrated.append(timeline)
    assert migrated, "the kill must migrate at least one request"
    timeline = migrated[0]
    # one id, two replicas, and the full phase vocabulary on each side
    assert len(timeline["replicas"]) >= 2
    names = [s["name"] for s in timeline["segments"]]
    assert names.count("prefill") >= 2 and names.count("decode") >= 2
    by_replica = {}
    for seg in timeline["segments"]:
        by_replica.setdefault(seg["replica"], []).append(seg["name"])
    for replica, segs in by_replica.items():
        assert "prefill" in segs or "queue_wait" in segs
    # the interrupted decode is attributed to the DEAD replica, and
    # every segment after the migrate marker belongs to a survivor
    migrate_ts = [m["ts_ms"] for m in timeline["markers"]
                  if m["name"] == "migrate"][0]
    dead_name = [m for m in timeline["markers"]
                 if m["name"] == "migrate"][0]["replica"]
    for seg in timeline["segments"]:
        if seg["start_ms"] > migrate_ts:
            assert seg["replica"] != dead_name
    # lanes recycled: nothing still leased after the fleet drained
    assert tracer._req_lanes == {}


def test_fleet_observability_e2e_demo(gpt, devices):
    """The acceptance scenario: replica crash + latency spike under a
    seeded FaultPlan, with the exporter serving live counters over
    HTTP, trace_report --request reconstructing a migrated request's
    waterfall from the written trace file, and the SLO monitor firing
    a slo_alert that is visible in the Chrome trace AND the registry
    snapshot."""
    import urllib.request

    from skycomputing_tpu import telemetry
    from skycomputing_tpu.telemetry import SloMonitor, SloTarget
    from skycomputing_tpu.telemetry.analysis import (
        load_events,
        request_ids,
        request_timeline,
    )
    from tools.trace_report import main as report_main

    layer_cfgs, params, fwd = gpt
    plan = FaultPlan(
        [dict(iter=6, kind="replica_crash", replica=0),
         dict(iter=14, kind="latency_spike", replica=1, seconds=0.25,
              duration=3)],
        seed=0,
    )
    fleet = ServingFleet(
        layer_cfgs, params, replicas=3,
        engine_kwargs=dict(num_slots=2, max_len=64, buckets=(8, 16),
                           max_concurrency=2),
        # sick detection OFF (fast_supervisor's default): the spike must
        # BURN the SLO rather than be healed away before the monitor
        # sees it
        supervisor=fast_supervisor(),
        fault_injector=FleetFaultInjector(plan),
        devices=devices,
        slo=SloMonitor([
            SloTarget(name="tpot_p95", metric="fleet.tpot_p95_s",
                      threshold=0.05, budget=0.25, fast_window=1,
                      slow_window=4),
            SloTarget(name="heal_budget",
                      metric="fleet.reform_failures",
                      threshold=100.0, kind="rate", fast_window=1,
                      slow_window=8),
        ]),
    )
    # the monitor is wired as the optional signal on both consumers
    assert fleet.admission.slo_monitor is fleet.slo
    assert fleet.supervisor.slo_monitor is fleet.slo
    assert "slo" in fleet.metrics
    exporter = fleet.start_exporter()
    rng = np.random.default_rng(12)
    requests = mixed_requests(
        rng,
        [(5, 16), (3, 14), (12, 12), (7, 15), (16, 13), (2, 17),
         (6, 12), (9, 14)],
    )
    import tempfile

    tracer = telemetry.enable_tracing()
    try:
        outputs = fleet.run(requests)
        with tempfile.TemporaryDirectory() as tmp:
            trace_path = tracer.write(f"{tmp}/fleet.trace.json")
            telemetry.disable_tracing()

            # 1. every accepted request still finishes token-identical
            assert_identity(fwd, requests, outputs)
            assert fleet.stats.migrations > 0
            assert fleet.stats.reforms >= 1

            # 2. the exporter's /metrics shows the fleet's live
            #    counters (and the SLO source) over real HTTP
            with urllib.request.urlopen(
                f"{exporter.url}/metrics", timeout=5
            ) as response:
                body = response.read().decode()
            assert "# TYPE skytpu_fleet_submitted counter" in body
            assert f"skytpu_fleet_submitted {len(requests)}" in body
            assert "skytpu_fleet_migrations" in body
            assert "skytpu_replica0_finished" in body
            assert "skytpu_slo_alerts_total" in body
            with urllib.request.urlopen(
                f"{exporter.url}/healthz", timeout=5
            ) as response:
                health = json.loads(response.read().decode())
            assert set(health["replicas"]) == {
                "replica0", "replica1", "replica2"
            }
            assert health["status"] in ("ok", "degraded")

            # 3. the SLO monitor fired during the spike: visible in the
            #    Chrome trace AND the registry snapshot
            events = load_events(trace_path)
            alerts = [ev for ev in events
                      if ev.get("name") == "slo_alert"]
            assert alerts, "the latency spike must burn the TPOT SLO"
            assert alerts[0]["args"]["target"] == "tpot_p95"
            snap = fleet.metrics.snapshot()
            assert snap["slo"]["alerts_total"] >= 1
            assert "tpot_p95" in fleet.slo.fired_ever
            assert "heal_budget" not in fleet.slo.fired_ever
            # the time-series behind it recorded the whole run
            assert fleet.timeseries.samples == fleet.stats.ticks
            assert fleet.timeseries.latest("fleet.migrations") \
                == fleet.stats.migrations

            # 4. trace_report --request reconstructs a migrated
            #    request's full waterfall from the written file
            migrated_ids = [
                rid for rid in request_ids(events)
                if request_timeline(events, rid)["migrations"] >= 1
            ]
            assert migrated_ids
            timeline = request_timeline(events, migrated_ids[0])
            assert timeline["complete"]
            assert timeline["orphan_spans"] == 0
            assert len(timeline["replicas"]) >= 2
            assert report_main(
                [trace_path, "--request", str(migrated_ids[0])]
            ) == 0
    finally:
        telemetry.disable_tracing()
        fleet.stop_exporter()


def test_slo_firing_tightens_admission_and_supervisor(gpt, devices):
    """The control couplings: a firing monitor halves the pending
    bound (visible in the decision detail) and makes the supervisor
    check every tick regardless of check_every."""

    class _FakeMonitor:
        firing = ("ttft",)

    adm = AdmissionController(max_pending=8)
    assert adm.pending_bound(0) == 8
    adm.slo_monitor = _FakeMonitor()
    assert adm.pending_bound(0) == 4  # slo_tighten=0.5 default
    decision = adm.decide(pending=4, capacity_slots=4)
    assert not decision.admitted and decision.reason == QUEUE_FULL
    assert decision.detail["slo_tightened"] is True
    adm.slo_monitor = None
    assert adm.decide(pending=4, capacity_slots=4,
                      priority="interactive").admitted
    # factor-scaled bounds tighten too, and never to zero
    auto = AdmissionController(queue_factor=2.0,
                               slo_monitor=_FakeMonitor(),
                               slo_tighten=0.25)
    assert auto.pending_bound(8) == 4
    assert auto.pending_bound(0) == 1
    with pytest.raises(ValueError, match="slo_tighten"):
        AdmissionController(slo_tighten=0.0)

    # supervisor: check_every=1000 would normally skip every poll;
    # the firing monitor forces the look, catching the dead replica
    layer_cfgs, params, fwd = gpt
    fleet = ServingFleet(
        layer_cfgs, params, replicas=2,
        engine_kwargs=dict(num_slots=2, max_len=64, buckets=(8, 16)),
        supervisor=fast_supervisor(check_every=1000),
        fault_injector=FleetFaultInjector(FaultPlan(
            [dict(iter=2, kind="replica_crash", replica=0)], seed=0
        )),
        devices=devices,
    )
    fleet.supervisor.slo_monitor = _FakeMonitor()
    rng = np.random.default_rng(13)
    requests = mixed_requests(rng, [(5, 8), (3, 6), (7, 7), (6, 5)])
    outputs = fleet.run(requests)
    assert_identity(fwd, requests, outputs)
    assert fleet.stats.reforms == 1  # caught despite check_every=1000


def test_replica_counters_stay_monotonic_across_reform(gpt, devices):
    """The fleet registry's per-replica source never shows a counter
    reset: a re-formed replica's fresh engine starts at zero, but
    stats_snapshot carries the prior generation's totals forward."""
    layer_cfgs, params, fwd = gpt
    fleet = ServingFleet(
        layer_cfgs, params, replicas=2,
        engine_kwargs=dict(num_slots=2, max_len=64, buckets=(8, 16)),
        supervisor=fast_supervisor(),
        fault_injector=FleetFaultInjector(FaultPlan(
            [dict(iter=5, kind="replica_crash", replica=0)], seed=0
        )),
        devices=devices,
    )
    ts = fleet.enable_timeseries(window=512)
    rng = np.random.default_rng(14)
    requests = mixed_requests(
        rng, [(5, 12), (3, 10), (7, 11), (6, 9), (9, 10), (4, 8)]
    )
    outputs = fleet.run(requests)
    assert_identity(fwd, requests, outputs)
    assert fleet.replicas[0].generation == 1
    # the engine reset, the replica's registered source did not
    rep = fleet.replicas[0]
    carried = rep._carried
    assert carried["iterations"] > 0
    snap = rep.stats_snapshot()
    assert snap["iterations"] == (carried["iterations"]
                                  + rep.engine.stats.iterations)
    assert snap["generation"] == 1
    # every sampled counter series is non-decreasing through the heal
    from skycomputing_tpu.serving.engine import ServingStats

    for field in ("iterations", "decode_tokens", "generated_tokens"):
        assert ServingStats.FIELD_TYPES[field] == "counter"
        values = ts.values(f"replica0.{field}")
        assert values, f"no samples for replica0.{field}"
        assert all(b >= a for a, b in zip(values, values[1:])), (
            f"replica0.{field} went backwards across the re-form"
        )


# --------------------------------------------------------------------------
# fault vocabulary (seeded-determinism contract)
# --------------------------------------------------------------------------


def test_fleet_fault_vocabulary_validation():
    # required fields enforced at plan construction
    with pytest.raises(ValueError, match="missing required field"):
        FaultPlan([dict(iter=0, kind="replica_crash")])
    with pytest.raises(ValueError, match="missing required field"):
        FaultPlan([dict(iter=0, kind="slot_leak")])
    # each applier rejects the other's vocabulary at construction
    fleet_plan = FaultPlan(
        [dict(iter=0, kind="replica_crash", replica=0)]
    )
    trainer_plan = FaultPlan(
        [dict(iter=0, kind="slowdown", worker=0, factor=2.0)]
    )
    with pytest.raises(ValueError, match="FleetFaultInjector"):
        FaultInjectionHook(fleet_plan)
    with pytest.raises(ValueError, match="FaultInjectionHook"):
        FleetFaultInjector(trainer_plan)
    FleetFaultInjector(fleet_plan)  # its own vocabulary is fine
    # replica indices are range-checked on the first tick, before
    # anything fires — not 50 ticks into a chaos run
    injector = FleetFaultInjector(FaultPlan(
        [dict(iter=40, kind="replica_crash", replica=7)]
    ))

    class _Fleet:
        tick = 0
        replicas = [object(), object()]

    with pytest.raises(ValueError, match="replica indices \\[7\\]"):
        injector.on_tick(_Fleet())


def test_successful_reforms_refund_the_budget(gpt, devices):
    """max_reforms bounds CONSECUTIVE failures: a fleet that keeps
    proving it can heal a replica must not retire it after N lifetime
    faults."""
    layer_cfgs, params, fwd = gpt
    plan = FaultPlan(
        [dict(iter=4, kind="replica_crash", replica=0),
         dict(iter=14, kind="replica_crash", replica=0),
         dict(iter=24, kind="replica_crash", replica=0)],
        seed=0,
    )
    fleet = ServingFleet(
        layer_cfgs, params, replicas=2,
        engine_kwargs=dict(num_slots=2, max_len=64, buckets=(8, 16),
                           max_concurrency=2),
        supervisor=fast_supervisor(max_reforms=2),
        fault_injector=FleetFaultInjector(plan),
        devices=devices,
    )
    rng = np.random.default_rng(11)
    requests = mixed_requests(
        rng, [(5, 16), (3, 14), (7, 15), (6, 12), (9, 13), (4, 11)]
    )
    outputs = fleet.run(requests)
    assert_identity(fwd, requests, outputs)
    # three successful heals of the same replica under max_reforms=2
    assert fleet.stats.reforms == 3
    assert fleet.replicas[0].state == HEALTHY
    assert fleet.replicas[0].generation == 3


def test_latency_spike_unpinned_seconds_is_seeded():
    """An event that leaves ``seconds`` open draws from the plan's
    generator: same seed, same spike — the determinism contract."""
    draws = []
    for _ in range(2):
        plan = FaultPlan(
            [dict(iter=0, kind="latency_spike", replica=0)], seed=11
        )
        injector = FleetFaultInjector(plan)

        class _Replica:
            name = "r0"

            def inject_stall(self, seconds, clear_at_tick=None):
                draws.append(seconds)

        class _Fleet:
            tick = 0
            replicas = [_Replica()]

            def replica_by_index(self, i):
                return self.replicas[i]

        injector.on_tick(_Fleet())
        assert injector.applied[0]["seconds"] == draws[-1]
    assert draws[0] == draws[1] > 0
    assert FaultPlan([], seed=11).draw_spike_seconds() == draws[0]
