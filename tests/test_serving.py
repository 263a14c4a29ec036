"""Serving-engine contracts (CPU-deterministic, tier-1).

The continuous-batching engine's correctness story is token identity:
whatever the scheduler does — mixed-length batches, requests joining and
leaving mid-decode, exhaustion, preemption — every request's output
must equal the one-shot full-forward ``generate`` for that prompt.  The
performance story is the compile discipline: after one warmup pass per
prompt bucket, the steady state pins ZERO XLA recompiles via
``xla_compile_count()``.
"""

import jax
import numpy as np
import pytest

from skycomputing_tpu.builder import build_layer_stack
from skycomputing_tpu.models.gpt import (
    GptConfig,
    generate,
    generate_cached,
    gpt_layer_configs,
)
from skycomputing_tpu.parallel.pipeline import xla_compile_count
from skycomputing_tpu.serving import (
    PagedKVCachePool,
    Request,
    RowAllocator,
    ServingEngine,
    ServingStats,
    ShapeBucketer,
)

pytestmark = pytest.mark.serving


def paged_engine(layer_cfgs, params, **kw):
    """An engine with small-test defaults (pages of 8)."""
    base = dict(num_slots=3, max_len=48, buckets=(8, 16),
                kv_layout="paged", page_size=8)
    base.update(kw)
    return ServingEngine(layer_cfgs, params, **base)


@pytest.fixture(scope="module")
def gpt():
    """Tiny GPT + host params + jitted one-shot forward reference."""
    cfg = GptConfig(vocab_size=512, hidden_size=64, num_hidden_layers=2,
                    num_attention_heads=2, max_position_embeddings=64,
                    dropout_prob=0.0, dtype="float32")
    layer_cfgs = gpt_layer_configs(cfg, deterministic=True)
    stack = build_layer_stack(layer_cfgs)
    params = stack.init(jax.random.key(7), np.ones((1, 5), np.int32))
    fwd = jax.jit(lambda ids: stack.apply(params, ids))
    return layer_cfgs, params, fwd


def reference(fwd, request):
    """One-shot greedy decode of the request's prompt."""
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


# --------------------------------------------------------------------------
# token identity
# --------------------------------------------------------------------------


@pytest.mark.parametrize("engine_kw, specs", [
    # three rows for six requests, over a 2-stage pipeline: rows run out
    pytest.param(
        dict(num_slots=3, max_len=64, prefill_batch=2, partition=[2, 4],
             max_concurrency=3),
        [(5, 9), (3, 4), (12, 7), (7, 1), (16, 6), (2, 11)],
        id="rows_exhausted_two_stages"),
    # eight rows over a pool of six pages: pages run out
    pytest.param(
        dict(max_len=48, page_size=8, num_pages=6, max_concurrency=8),
        [(4, 6), (5, 3), (12, 8), (6, 2), (2, 5), (9, 4)],
        id="pages_exhausted"),
])
def test_mixed_length_batch_token_identity(gpt, devices, engine_kw, specs):
    """More mixed-length, mixed-generation requests than the engine can
    seat at once: admission queues on the exhausted resource (never
    errors, never corrupts), the pool never over-allocates, every
    request still finishes token-identical to its one-shot decode, and
    the refcount audit passes after the drain."""
    layer_cfgs, params, fwd = gpt
    engine = ServingEngine(layer_cfgs, params, buckets=(8, 16),
                           devices=devices[:2], **engine_kw)
    requests = mixed_requests(np.random.default_rng(0), specs)
    for r in requests:
        engine.submit(r)
    pages_seen = []
    while engine.has_work():
        engine.step()
        pages_seen.append(engine._pool.pages_in_use)
    assert max(pages_seen) <= engine.num_pages  # never over-allocates
    assert engine.stats.queue_stalls > 0  # exhaustion queued
    assert engine.stats.finished == len(requests)
    assert engine.stats.queue_depth == 0
    for r in requests:
        np.testing.assert_array_equal(r.output(), reference(fwd, r))
    engine._pool.check_consistency()


def test_join_and_leave_mid_decode(gpt):
    """A request joining while others are mid-decode, and requests
    finishing early, never perturb any other request's token stream."""
    layer_cfgs, params, fwd = gpt
    engine = ServingEngine(
        layer_cfgs, params, num_slots=3, max_len=64, buckets=(8,),
    )
    rng = np.random.default_rng(1)
    long_a, short, long_b = mixed_requests(
        rng, [(5, 12), (4, 3), (6, 10)]
    )
    engine.submit(long_a)
    engine.submit(short)
    for _ in range(4):
        engine.step()
    # `short` left the batch (finished) while `long_a` is mid-decode
    assert short.done and short.status == "finished"
    assert not long_a.done
    engine.submit(long_b)  # joins the running batch between decode steps
    engine.step()
    assert long_b.status == "running" and not long_a.done
    engine.run()
    for r in (long_a, short, long_b):
        np.testing.assert_array_equal(r.output(), reference(fwd, r))


def test_slot_exhaustion_queues_not_crashes(gpt):
    layer_cfgs, params, fwd = gpt
    engine = ServingEngine(
        layer_cfgs, params, num_slots=2, max_len=64, buckets=(8,),
        max_concurrency=2,
    )
    rng = np.random.default_rng(2)
    requests = mixed_requests(
        rng, [(4, 6), (5, 3), (3, 8), (6, 2), (2, 5)]
    )
    for r in requests:
        engine.submit(r)
    assert engine.stats.queue_depth == 5
    occupancies = []
    while engine.has_work():
        engine.step()
        occupancies.append(engine.stages[0].pool.used_slots)
    assert max(occupancies) <= 2  # the pool never over-allocates
    assert engine.stats.queue_stalls > 0  # exhaustion queued
    assert engine.stats.finished == 5
    for r in requests:
        np.testing.assert_array_equal(r.output(), reference(fwd, r))


def test_preemption_requeues_with_stream_intact(gpt):
    """Recomputation preemption: the evicted request re-queues, rebuilds
    its KV prefix on re-admission, and its final stream is untouched."""
    layer_cfgs, params, fwd = gpt
    engine = ServingEngine(
        layer_cfgs, params, num_slots=2, max_len=64, buckets=(8, 16),
    )
    rng = np.random.default_rng(3)
    victim, other = mixed_requests(rng, [(5, 10), (3, 4)])
    engine.submit(victim)
    engine.submit(other)
    for _ in range(3):
        engine.step()
    assert not victim.done
    engine.preempt(victim.request_id)
    assert victim.slot is None and victim.preemptions == 1
    assert engine.stats.preemptions == 1
    engine.run()
    np.testing.assert_array_equal(victim.output(), reference(fwd, victim))
    np.testing.assert_array_equal(other.output(), reference(fwd, other))


def test_default_engine_is_paged_and_token_identical(gpt):
    """``ServingEngine(cfgs, params)`` with no layout argument serves
    from a page pool (the derived defaults: 4 rows' worth of whole
    spans, 16 decode rows) and a mixed batch matches
    ``generate_cached``, the single-request reference, token for
    token."""
    layer_cfgs, params, _ = gpt
    stack = build_layer_stack(layer_cfgs)
    engine = ServingEngine(layer_cfgs, params)
    assert isinstance(engine._pool, PagedKVCachePool)
    # max_position_embeddings = 64 clamps the default max_len of 128
    assert (engine.page_size, engine.max_pages_per_request,
            engine.max_len) == (16, 4, 64)
    assert (engine.num_pages, engine.max_concurrency,
            engine.num_slots) == (16, 16, 16)
    assert engine.free_slots == 16 and engine.attn_impl == "xla"
    assert engine._health_snapshot()["kv_layout"] == "paged"
    requests = mixed_requests(
        np.random.default_rng(8), [(5, 9), (3, 4), (40, 7), (20, 1), (60, 4)]
    )
    outputs = engine.run(requests)
    for r in requests:
        np.testing.assert_array_equal(
            outputs[r.request_id],
            generate_cached(stack, params, r.prompt, r.max_new_tokens,
                            context_length=64)[0],
        )
    engine._pool.check_consistency()


@pytest.mark.parametrize("removed", [
    dict(kv_layout="slot"),
    dict(static_batching=True),
    dict(gather_pages="full"),
], ids=lambda kw: next(iter(kw)))
def test_slot_layout_is_rejected_by_name(gpt, removed):
    """The slot layout and the two options that only its harness set
    are gone: the layout by a ``ValueError`` that says so, the options
    as the unexpected keywords they now are."""
    layer_cfgs, params, _ = gpt
    if "kv_layout" in removed:
        with pytest.raises(ValueError, match="slot layout was removed"):
            ServingEngine(layer_cfgs, params, **removed)
    else:
        with pytest.raises(TypeError, match="unexpected keyword"):
            ServingEngine(layer_cfgs, params, **removed)


# --------------------------------------------------------------------------
# compile discipline
# --------------------------------------------------------------------------


def _warm_each_bucket(rng):
    # one request per bucket; the second decodes past 16 positions, so
    # the next page-table width is warm too
    return [mixed_requests(rng, [(4, 3), (12, 6)])]


def _warm_buckets_and_a_prefix_hit(rng):
    # distinct leading tokens so the prefix cache cannot collapse a
    # bucket's tail into a smaller one, then a shared-prefix pair: the
    # 2nd hits the 1st's prefix, which warms the COW copy program
    runs = [
        [Request(prompt=np.full((b,), b + 1, np.int32), max_new_tokens=2)]
        for b in (8, 16)
    ]
    system = rng.integers(1, 512, (12,)).astype(np.int32)
    runs += [
        [Request(prompt=np.concatenate(
            [system, rng.integers(1, 512, (2,)).astype(np.int32)]),
            max_new_tokens=2)]
        for _ in range(2)
    ]
    return runs


@pytest.mark.parametrize("engine_kw, warmup, prefix_hits", [
    pytest.param(dict(num_slots=3, max_len=64, max_concurrency=3),
                 _warm_each_bucket, 0, id="pages_of_16"),
    pytest.param(dict(num_slots=3, max_len=48, page_size=8),
                 _warm_buckets_and_a_prefix_hit, 1,
                 id="pages_of_8_with_prefix_hits"),
])
def test_zero_steady_state_recompiles_after_bucket_warmup(
    gpt, engine_kw, warmup, prefix_hits
):
    """A warmup pass compiles every program (one step shape per bucket
    and table width, and the COW copy where a prefix is shared); a
    second, larger mixed wave then runs with ZERO XLA backend compiles,
    with tracing off and with tracing on."""
    layer_cfgs, params, fwd = gpt
    engine = ServingEngine(layer_cfgs, params, buckets=(8, 16),
                           prefill_batch=2, **engine_kw)
    rng = np.random.default_rng(4)
    for run in warmup(rng):
        engine.run(run)
    assert engine.stats.prefix_hits >= prefix_hits  # the warmup really hit
    warm = xla_compile_count()
    wave = mixed_requests(rng, [(6, 8), (2, 3), (15, 5), (9, 4), (11, 2)])
    outputs = engine.run(wave)
    assert xla_compile_count() == warm, (
        "steady-state serving recompiled after bucket warmup"
    )
    for r in wave:
        np.testing.assert_array_equal(
            outputs[r.request_id], reference(fwd, r)
        )
    # the pin must also hold WITH tracing on: instrumentation (telemetry
    # spans around prefill/decode) cannot perturb jit identity, and the
    # traced wave stays token-identical
    from skycomputing_tpu import telemetry

    # fresh snapshot: the reference() identity loop above jit-compiles
    # the one-shot fwd, which is NOT engine work — counting from `warm`
    # would make this assertion order-dependent across test selection
    warm_traced = xla_compile_count()
    telemetry.enable_tracing()
    try:
        traced_wave = mixed_requests(rng, [(5, 4), (13, 3)])
        traced_out = engine.run(traced_wave)
        assert xla_compile_count() == warm_traced, (
            "tracing-enabled serving step recompiled"
        )
    finally:
        telemetry.disable_tracing()
    for r in traced_wave:
        np.testing.assert_array_equal(
            traced_out[r.request_id], reference(fwd, r)
        )


# --------------------------------------------------------------------------
# admission / pool contracts
# --------------------------------------------------------------------------


def test_bucketer_contract():
    b = ShapeBucketer((16, 8, 8))  # dedup + sort
    assert b.buckets == (8, 16)
    assert b.bucket_for(1) == 8 and b.bucket_for(8) == 8
    assert b.bucket_for(9) == 16
    with pytest.raises(ValueError, match="exceeds the largest bucket"):
        b.bucket_for(17)
    ids, lengths = b.pad_batch(
        [np.array([1, 2, 3], np.int32)], 8, rows=2, pad_id=0
    )
    assert ids.shape == (2, 8) and lengths.tolist() == [3, 1]
    assert ids[0, :3].tolist() == [1, 2, 3] and ids[0, 3:].sum() == 0


def test_row_allocator_contract():
    rows = RowAllocator(2)
    assert rows.free_slots == 2 and rows.occupancy == 0.0
    a, b = rows.allocate(), rows.allocate()
    assert {a, b} == {0, 1} and rows.used_slots == 2
    assert rows.allocate() is None  # exhaustion is a None, not a raise
    rows.release(a)
    with pytest.raises(ValueError, match="double-released"):
        rows.release(a)
    with pytest.raises(ValueError, match="out of range"):
        rows.release(2)
    rows.acquire(a)  # claims one specific free row
    with pytest.raises(ValueError, match="not free"):
        rows.acquire(a)
    assert rows.total_mb() == 0.0  # rows own no device memory
    with pytest.raises(ValueError, match="at least 1 row"):
        RowAllocator(0)


def test_engine_preflight_rejects_over_budget_kv_slabs(gpt, devices):
    """An allocation whose page pool blows a worker's mem_limit dies at
    engine construction — before any slab allocates or program compiles
    — with the serving operating point in the diagnostic."""
    from skycomputing_tpu.analysis.plan_check import PlanError
    from skycomputing_tpu.dynamics import WorkerManager

    layer_cfgs, params, _ = gpt
    wm = WorkerManager()
    wm.load_worker_pool_from_config([
        dict(name=f"n{i}", device_config=dict(device_index=i),
             extra_config=dict(mem_limit=0.05))
        for i in range(2)
    ])
    cursor = 0
    for w, c in zip(wm.worker_pool, [3, 3]):
        w.model_config = layer_cfgs[cursor:cursor + c]
        w.order = w.rank + 1
        cursor += c
    with pytest.raises(PlanError, match="KV pages"):
        ServingEngine(
            layer_cfgs, params, num_slots=64, max_len=64, buckets=(8,),
            worker_manager=wm, devices=devices,
        )
    # the same plan passes with the budgets lifted
    for w in wm.worker_pool:
        w.extra_config["mem_limit"] = 10_000.0
    ServingEngine(
        layer_cfgs, params, num_slots=64, max_len=64, buckets=(8,),
        worker_manager=wm, devices=devices,
    )


def test_engine_rejects_oversized_request(gpt):
    layer_cfgs, params, _ = gpt
    engine = ServingEngine(
        layer_cfgs, params, num_slots=2, max_len=32, buckets=(8, 16),
    )
    with pytest.raises(ValueError, match="exceed max_len"):
        engine.submit(Request(prompt=np.arange(1, 17, dtype=np.int32),
                              max_new_tokens=20))
    with pytest.raises(ValueError, match="exceeds the largest bucket"):
        engine.submit(Request(prompt=np.arange(1, 21, dtype=np.int32),
                              max_new_tokens=2))


# --------------------------------------------------------------------------
# SLO metrics
# --------------------------------------------------------------------------


def test_serving_stats_slo_surface(gpt):
    layer_cfgs, params, _ = gpt
    engine = ServingEngine(
        layer_cfgs, params, num_slots=2, max_len=64, buckets=(8,),
    )
    rng = np.random.default_rng(5)
    requests = mixed_requests(rng, [(4, 5), (6, 3), (3, 4)])
    engine.run(requests)
    snap = engine.stats.snapshot()
    assert snap["finished"] == 3 and snap["admitted"] == 3
    assert len(engine.stats.ttft_s) == 3
    assert all(t > 0 for t in engine.stats.ttft_s)
    assert snap["ttft_p95_s"] >= snap["ttft_p50_s"] > 0
    assert snap["tokens_per_s"] > 0
    assert snap["generated_tokens"] == 5 + 3 + 4
    # per-request SLO stamps survive on the request objects
    for r in requests:
        assert r.ttft_s() > 0 and r.tpot_s() is not None


# --------------------------------------------------------------------------
# decode-cost allocation
# --------------------------------------------------------------------------


def test_decode_profile_charges_kv_slabs(gpt):
    from skycomputing_tpu.serving import DecodeModelBenchmarker

    layer_cfgs, _, _ = gpt
    small = DecodeModelBenchmarker(layer_cfgs, slots=2, max_len=32)
    big = DecodeModelBenchmarker(layer_cfgs, slots=8, max_len=32)
    costs_s, mems_s = small.benchmark()
    costs_b, mems_b = big.benchmark()
    assert len(costs_s) == len(layer_cfgs)
    assert all(c > 0 for c in costs_s)
    for cfg, ms, mb in zip(layer_cfgs, mems_s, mems_b):
        if cfg["layer_type"] == "GptBlock_Attn":
            assert mb > ms  # slab memory scales with the slot count
    assert small.operating_point == dict(slots=2, max_len=32)


def test_serving_allocate_balances_decode_costs(gpt, devices):
    from skycomputing_tpu.dataset import RandomTensorGenerator
    from skycomputing_tpu.dynamics import (
        Allocator,
        DeviceBenchmarker,
        WorkerManager,
    )
    from skycomputing_tpu.serving import DecodeModelBenchmarker

    layer_cfgs, params, fwd = gpt
    wm = WorkerManager()
    wm.load_worker_pool_from_config([
        dict(name=f"n{i}", device_config=dict(device_index=i),
             extra_config={})
        for i in range(2)
    ])
    allocator = Allocator(
        layer_cfgs, wm, None,
        DeviceBenchmarker(
            wm, RandomTensorGenerator(size=(4, 64)),
            [dict(layer_type="MatmulStack", features=64, depth=1)],
            iterations=2,
        ),
    )
    allocator._cost_override = [1.0] * len(layer_cfgs)  # training relic
    dec = DecodeModelBenchmarker(layer_cfgs, slots=3, max_len=64)
    allocator.serving_allocate(dec, max_time=5)
    # the training-calibrated override is restored, not clobbered
    assert allocator._cost_override == [1.0] * len(layer_cfgs)
    counts = [
        len(w.model_config)
        for w in sorted(wm.worker_pool, key=lambda w: w.rank)
        if w.model_config
    ]
    assert sum(counts) == len(layer_cfgs) and all(c > 0 for c in counts)

    # the serving-balanced allocation actually serves, token-identically
    engine = ServingEngine(
        layer_cfgs, params, num_slots=3, max_len=64, buckets=(8, 16),
        worker_manager=wm, devices=devices,
    )
    rng = np.random.default_rng(6)
    requests = mixed_requests(rng, [(5, 4), (11, 3)])
    outputs = engine.run(requests)
    for r in requests:
        np.testing.assert_array_equal(
            outputs[r.request_id], reference(fwd, r)
        )


# --------------------------------------------------------------------------
# page pool + prefix reuse
# --------------------------------------------------------------------------


def test_paging_pool_contract():
    """Host bookkeeping: grants charge ceil(len/page_size) pages, a
    radix hit maps shared pages by refcount with a COW clone for the
    partial tail page, exhaustion returns None without mutating, LRU
    eviction reclaims cache-only pages, and the refcount audit holds
    at every step."""
    pool = PagedKVCachePool(num_pages=8, page_size=4,
                            max_pages_per_request=6)
    g1 = pool.acquire(1, list(range(10)), 15)
    assert len(g1.page_table) == 4 and g1.shared_tokens == 0
    pool.register_prefix(1, list(range(10)))
    pool.check_consistency()
    g2 = pool.acquire(2, list(range(10)) + [99, 98], 14)
    assert g2.shared_tokens == 10 and g2.shared_pages == 2
    assert g2.page_table[:2] == g1.page_table[:2]  # mapped, not copied
    assert g2.cow_src == g1.page_table[2]  # partial page -> COW clone
    assert g2.cow_dst == g2.new_pages[0]
    assert pool.prefix_hits == 1 and pool.prefix_tokens_reused == 10
    pool.check_consistency()
    # uncoverable acquire: None, nothing mutated (cache not spent)
    evictions = pool.prefix_evictions
    assert pool.acquire(3, [7, 7, 7], 20) is None
    assert pool.prefix_evictions == evictions
    pool.check_consistency()
    # cache retention: releasing the donor keeps its prompt pages
    assert pool.release(1) == 1
    # pressure evicts the LRU entry and the grant lands
    assert pool.acquire(3, [7, 7, 7], 16) is not None
    assert pool.prefix_evictions == evictions + 1
    pool.release(2)
    pool.release(3)
    pool.check_consistency()
    assert pool.free_pages == 8
    with pytest.raises(KeyError):
        pool.release(42)


def test_paged_prefix_reuse_cow_identity(gpt):
    """A request sharing a system prompt with an earlier one is
    token-identical to its unshared twin, while the radix cache counts
    the hit, the reused tokens, and the COW clone that kept the shared
    partial page read-only."""
    layer_cfgs, params, fwd = gpt
    engine = paged_engine(layer_cfgs, params, buckets=(8, 16, 32))
    rng = np.random.default_rng(12)
    system = rng.integers(1, 512, (18,)).astype(np.int32)
    first = Request(
        prompt=np.concatenate(
            [system, rng.integers(1, 512, (3,)).astype(np.int32)]),
        max_new_tokens=6,
    )
    engine.run([first])
    assert engine.stats.prefix_hits == 0
    twin_prompt = np.concatenate(
        [system, rng.integers(1, 512, (4,)).astype(np.int32)]
    )
    shared = Request(prompt=twin_prompt.copy(), max_new_tokens=6)
    engine.run([shared])
    snap = engine.stats.snapshot()
    assert snap["prefix_hits"] == 1
    # token-granular sharing: the whole 18-token system prompt plus the
    # matching span of the first request's tail (if any) is reused
    assert snap["prefix_tokens_reused"] >= 18
    assert snap["cow_copies"] >= 1  # 18 % 8 != 0 -> partial page clone
    # the shared-prefix request equals its UNSHARED twin: one-shot
    # decode of the same prompt on a fresh reference
    np.testing.assert_array_equal(shared.output(), reference(fwd, shared))
    np.testing.assert_array_equal(first.output(), reference(fwd, first))
    engine._pool.check_consistency()


def test_paged_swap_and_recompute_preempt_identity(gpt):
    """Swap-preempted and recompute-preempted requests both resume
    with identical token streams; swap round-trips through the host
    pool without prefill, recompute re-prefills (and may hit its own
    cached prompt)."""
    layer_cfgs, params, fwd = gpt
    engine = paged_engine(layer_cfgs, params)
    rng = np.random.default_rng(13)
    swap_victim, recompute_victim, bystander = mixed_requests(
        rng, [(6, 10), (5, 9), (4, 4)]
    )
    for r in (swap_victim, recompute_victim, bystander):
        engine.submit(r)
    for _ in range(3):
        engine.step()
    assert not swap_victim.done and not recompute_victim.done
    # an unknown mode is rejected BEFORE any state is touched — a
    # fall-through here would tear the request down un-requeueable
    with pytest.raises(ValueError, match="preempt mode"):
        engine.preempt(swap_victim.request_id, mode="Swap")
    assert swap_victim.request_id in engine._running
    engine.preempt(swap_victim.request_id, mode="swap")
    engine.preempt(recompute_victim.request_id, mode="recompute")
    assert engine.stats.swap_outs == 1
    assert swap_victim.request_id in engine._swapped
    engine.run()
    assert engine.stats.swap_ins == 1
    assert not engine._swapped
    for r in (swap_victim, recompute_victim, bystander):
        np.testing.assert_array_equal(r.output(), reference(fwd, r))
    engine._pool.check_consistency()


# stamped by the parent of the merged-heads pool (slabs stored
# [num_pages, page_size, heads, head_dim]) for the run below, on this
# suite's CPU backend: the swap record hashes contiguous bytes, which
# the stored shape does not change
SWAP_CHECKSUMS_OF_THE_4D_POOL = {
    None: "36bd844b8e1c26f1f5e7132521f8b124"
          "d554f8167d24daeab4adbdb309876913",
    "int8": "714968c5b2058487c8eb76d49a9d19e1"
            "3f32c6968a793663c362de724a1addfc",
}


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_paged_swap_record_bytes_do_not_depend_on_the_stored_shape(
    gpt, kv_dtype
):
    """A swap-out of the same tokens parks the same bytes under the
    same checksum whether the pool stores ``[.., heads, head_dim]`` or
    ``[.., heads * head_dim]``: page rows are contiguous either way."""
    from skycomputing_tpu.serving.engine import _swap_record_checksum
    from skycomputing_tpu.serving.kv_cache import QuantizedPages

    layer_cfgs, params, _ = gpt
    engine = paged_engine(layer_cfgs, params, kv_dtype=kv_dtype)
    victim, *others = mixed_requests(
        np.random.default_rng(13), [(6, 10), (5, 9), (4, 4)]
    )
    for r in (victim, *others):
        engine.submit(r)
    for _ in range(3):
        engine.step()
    engine.preempt(victim.request_id, mode="swap")
    record = engine._swapped[victim.request_id]
    assert record["checksum"] == SWAP_CHECKSUMS_OF_THE_4D_POOL[kv_dtype]

    def as_4d(host):
        if isinstance(host, QuantizedPages):
            return QuantizedPages(as_4d(host.values), host.scale)
        assert host.ndim == 3  # [table width, page_size, heads * head_dim]
        return host.reshape(host.shape[:2] + (2, -1))

    data_4d = [
        [(as_4d(k), as_4d(v)) for k, v in stage] for stage in record["data"]
    ]
    assert _swap_record_checksum(
        record["pages"], record["index"], data_4d
    ) == record["checksum"]
    engine.run()
    assert engine.stats.swap_ins == 1 and victim.done
    engine._pool.check_consistency()


def test_paged_admission_decouples_buckets_from_capacity(gpt):
    """Buckets are pure compile-shape classes: a short prompt padded to
    a bucket charges pages for its TRUE span, so four requests whose
    bucket-padded sizes exceed the pool all run concurrently on the
    pages their tokens actually need."""
    layer_cfgs, params, fwd = gpt
    # pool = 4 pages x 8 positions = 32 positions; each request spans
    # <= 8 positions (1 page) but pads to the 16-bucket for compile
    engine = paged_engine(layer_cfgs, params, num_pages=4,
                          max_pages_per_request=2, buckets=(16,),
                          max_concurrency=4, prefill_batch=4)
    rng = np.random.default_rng(15)
    requests = mixed_requests(rng, [(5, 3), (6, 2), (4, 4), (5, 2)])
    for r in requests:
        engine.submit(r)
    engine.step()
    # all four admitted at once: 4 x bucket(16) = 64 padded positions
    # against a 32-position pool — bucket choice did not charge memory
    assert len(engine.running_requests) + engine.stats.finished == 4
    assert engine.stats.queue_stalls == 0
    engine.run()
    for r in requests:
        np.testing.assert_array_equal(r.output(), reference(fwd, r))
    # the other cap: two rows seat two requests however many pages and
    # however wide a prefill wave there are
    rows = ServingEngine(layer_cfgs, params, num_slots=2, max_len=32,
                         buckets=(16,), prefill_batch=4, max_concurrency=2)
    for r in mixed_requests(rng, [(5, 3), (6, 2), (4, 4), (5, 2)]):
        rows.submit(r)
    rows.step()
    assert len(rows.running_requests) + rows.stats.finished <= 2


def test_paged_default_span_clamps_to_position_table(gpt):
    """The derived max_pages_per_request never rounds the per-request
    span past max_position_embeddings: a max_len the model accepts
    must not be rejected by its own rounding to pages."""
    layer_cfgs, params, _ = gpt  # max_position_embeddings = 64
    engine = ServingEngine(
        layer_cfgs, params, num_slots=2, max_len=60, buckets=(8,),
        kv_layout="paged", page_size=24,
    )
    # ceil(60/24)=3 pages would span 72 > 64; clamped to 2 pages = 48
    assert engine.max_pages_per_request == 2 and engine.max_len == 48
    # an EXPLICIT over-span still errors (the caller asked for it)
    with pytest.raises(ValueError, match="max_position_embeddings"):
        ServingEngine(layer_cfgs, params, num_slots=2, max_len=60,
                      buckets=(8,), kv_layout="paged", page_size=24,
                      max_pages_per_request=3)


def test_paged_reconfigure_verify_then_apply(gpt):
    """Knob classes: bucket-only changes are eviction-free; a
    concurrency change evicts recomputation-style on the same pool; a
    geometry change rebuilds pool+slabs with counters banked (never
    backwards); infeasible points are rejected with the engine
    untouched."""
    layer_cfgs, params, fwd = gpt
    engine = paged_engine(layer_cfgs, params, max_concurrency=4)
    rng = np.random.default_rng(16)
    requests = mixed_requests(rng, [(5, 8), (3, 6), (6, 9)])
    for r in requests:
        engine.submit(r)
    for _ in range(3):
        engine.step()
    engine.reconfigure(buckets=(8, 16, 32))
    assert engine.stats.preemptions == 0  # bucket-only: no eviction
    engine.reconfigure(max_concurrency=6)
    assert engine.stats.preemptions > 0
    assert engine.num_slots == 6  # num_slots is the row count
    engine.step()
    hits_before = engine.stats.prefix_hits
    old_pool = engine._pool
    engine.reconfigure(num_pages=12)
    assert engine._pool is not old_pool and engine.num_pages == 12
    engine.run()
    for r in requests:
        np.testing.assert_array_equal(r.output(), reference(fwd, r))
    assert engine.stats.snapshot()["prefix_hits"] >= hits_before
    engine._pool.check_consistency()
    # rejection (knob verifier) leaves the engine untouched
    from skycomputing_tpu.analysis.plan_check import PlanError

    with pytest.raises(PlanError, match="max_pages_per_request"):
        engine.reconfigure(max_pages_per_request=100)
    assert engine.num_pages == 12


# --------------------------------------------------------------------------
# fused kernel + int8 KV pages (PR 12)
# --------------------------------------------------------------------------


def test_paged_gather_bound_live_identity(gpt):
    """The bounded live-width gather is pure shape bookkeeping: outputs
    are token-identical to ``generate_cached``'s stream, which reads
    each request's whole row, AND to one-shot generate — positions a
    narrower gather drops were exactly the ones the causal mask already
    zeroed."""
    layer_cfgs, params, fwd = gpt
    stack = build_layer_stack(layer_cfgs)
    requests = mixed_requests(
        np.random.default_rng(31), [(5, 8), (3, 4), (14, 6), (9, 3)]
    )
    engine = paged_engine(layer_cfgs, params)
    # the widest table (6 columns) is never asked for: 2 is the floor
    # (the 16-bucket) and 14 + 6 positions need 3, so 4
    assert engine.max_pages_per_request == 6
    outputs = engine.run(requests)
    assert engine.stats.attn_pages_table <= (
        engine.stats.iterations * engine.max_concurrency * 4
    )
    for r in requests:
        np.testing.assert_array_equal(
            outputs[r.request_id],
            generate_cached(stack, params, r.prompt, r.max_new_tokens,
                            context_length=64)[0],
        )
        np.testing.assert_array_equal(
            outputs[r.request_id], reference(fwd, r)
        )


def test_paged_attn_impl_pallas_identity_and_recompile_pin(gpt):
    """attn_impl="pallas" (interpret mode on CPU): greedy streams are
    token-identical to the XLA reference engine and to generate, and
    after bucket + span-width warmup the steady state pins ZERO XLA
    compiles — the recompile discipline extended to the kernel path."""
    layer_cfgs, params, fwd = gpt
    kw = dict(num_slots=2, max_len=32, buckets=(8,), prefill_batch=1,
              kv_layout="paged", page_size=8, max_pages_per_request=4,
              num_pages=12, max_concurrency=2)
    pallas = ServingEngine(layer_cfgs, params, attn_impl="pallas", **kw)
    assert pallas.attn_impl == "pallas"
    xla = ServingEngine(layer_cfgs, params, attn_impl="xla", **kw)
    for e in (pallas, xla):
        # bucket warm + span warm: a short prompt decoding across the
        # span sweeps every live-gather width through compilation
        e.run([Request(prompt=np.full((8,), 9, np.int32),
                       max_new_tokens=2)])
        e.run([Request(prompt=np.full((2,), 3, np.int32),
                       max_new_tokens=20)])
    warm = xla_compile_count()
    rng = np.random.default_rng(32)
    specs = [(5, 4), (3, 3)]
    p_reqs = mixed_requests(rng, specs)
    x_reqs = [
        Request(prompt=r.prompt.copy(), max_new_tokens=r.max_new_tokens)
        for r in p_reqs
    ]
    p_out = pallas.run(p_reqs)
    assert xla_compile_count() == warm, (
        "steady-state pallas serving recompiled after warmup"
    )
    x_out = xla.run(x_reqs)
    for pr, xr in zip(p_reqs, x_reqs):
        np.testing.assert_array_equal(
            p_out[pr.request_id], x_out[xr.request_id]
        )
        np.testing.assert_array_equal(
            p_out[pr.request_id], reference(fwd, pr)
        )


def test_paged_decode_counts_live_and_table_pages(gpt):
    """``attn_pages_live`` / ``attn_pages_table`` per decode tick,
    against a table worked out by hand: pages of 4, three rows, prompts
    of 14 and 5 tokens (so first queries at 14 and 5), the 16-bucket's
    four columns as the table's floor and eight once a row needs a
    fifth; an idle row costs the one page the kernel always reads."""
    layer_cfgs, params, _ = gpt
    engine = ServingEngine(
        layer_cfgs, params, num_slots=3, max_len=64, buckets=(8, 16),
        prefill_batch=2, kv_layout="paged", page_size=4,
        max_concurrency=3,
    )
    for length, new in ((14, 6), (5, 3)):
        engine.submit(Request(
            prompt=np.arange(1, length + 1, dtype=np.int32),
            max_new_tokens=new,
        ))
    by_hand = [
        # queries at    pages live       rows x table width
        (4 + 2 + 1, 3 * 4),  # 14, 5
        (4 + 2 + 1, 3 * 4),  # 15, 6: the short request's last token
        (5 + 1 + 1, 3 * 8),  # 16: a fifth page, the next width
        (5 + 1 + 1, 3 * 8),  # 17
        (5 + 1 + 1, 3 * 8),  # 18
    ]
    seen, before = [], (0, 0)
    while engine._running or engine._queue.depth:
        engine.step()
        now = (engine.stats.attn_pages_live, engine.stats.attn_pages_table)
        seen.append((now[0] - before[0], now[1] - before[1]))
        before = now
    assert seen == by_hand
    snap = engine.stats.snapshot()
    assert snap["attn_pages_live"] == 35 and snap["attn_pages_table"] == 96
    assert ServingStats.FIELD_TYPES["attn_pages_live"] == "counter"
    assert ServingStats.FIELD_TYPES["attn_pages_table"] == "counter"


# re-tiered slow: tier-1 wall-clock budget; the full run keeps it
@pytest.mark.slow
def test_paged_int8_agreement_and_observability(gpt):
    """kv_dtype="int8": bounded-error pages keep greedy streams in high
    positional agreement with the fp engine (exactness is NOT the
    contract — near-tie argmax flips compound), the quant counters
    move, /healthz names the active kv_dtype/attn_impl, the prefix-
    cache/COW path stays refcount-consistent, and generation lengths
    are untouched."""
    layer_cfgs, params, fwd = gpt
    rng = np.random.default_rng(33)
    specs = [(5, 9), (3, 4), (12, 7), (7, 5), (14, 6), (2, 8)]
    fp_reqs = mixed_requests(rng, specs)
    i8_reqs = [
        Request(prompt=r.prompt.copy(), max_new_tokens=r.max_new_tokens)
        for r in fp_reqs
    ]
    fp = paged_engine(layer_cfgs, params, prefill_batch=2)
    i8 = paged_engine(layer_cfgs, params, prefill_batch=2,
                      kv_dtype="int8")
    fp_out = fp.run(fp_reqs)
    i8_out = i8.run(i8_reqs)
    agree = total = 0
    for fr, ir in zip(fp_reqs, i8_reqs):
        x = fp_out[fr.request_id][len(fr.prompt):]
        y = i8_out[ir.request_id][len(ir.prompt):]
        assert x.size == y.size  # budgets untouched by quantization
        agree += int((x == y).sum())
        total += int(x.size)
    assert agree / total >= 0.5, (
        f"int8 greedy agreement {agree}/{total} below the gate"
    )
    stats = i8.stats
    assert stats.quantized_pages > 0 and stats.dequant_blocks > 0
    assert fp.stats.quantized_pages == 0  # fp engines never quantize
    snap = i8._health_snapshot()
    assert snap["kv_dtype"] == "int8" and snap["attn_impl"] == "xla"
    assert fp._health_snapshot()["kv_dtype"] == "float32"
    # shared-prefix COW on the quantized pool: the scale row clones
    # with the values (pool.cow_plan names both), refcounts audited
    system = rng.integers(1, 512, (12,)).astype(np.int32)
    for _ in range(2):
        i8.run([Request(prompt=np.concatenate(
            [system, rng.integers(1, 512, (3,)).astype(np.int32)]),
            max_new_tokens=3)])
    assert i8.stats.prefix_hits >= 1 and i8.stats.cow_copies >= 1
    i8._pool.check_consistency()
    assert i8._pool.kv_dtype == "int8"


def test_paged_kv_dtype_charging_and_validation(gpt):
    """The pre-flight charges int8 pools at the quantized byte width
    (values + scale slabs, the allocator's own formula) — ~4x below a
    float32 pool — and malformed kv_dtype knobs are rejected with
    named diagnostics, never silently mis-accounted."""
    from skycomputing_tpu.analysis.plan_check import (
        _serving_kv_profile,
    )
    from skycomputing_tpu.serving import (
        DecodeModelBenchmarker,
        paged_kv_mb_per_layer,
        paged_pool_mb,
    )

    layer_cfgs, params, _ = gpt
    fp = paged_kv_mb_per_layer(layer_cfgs, 12, 8)
    i8 = paged_kv_mb_per_layer(layer_cfgs, 12, 8, kv_dtype="int8")
    ratio = sum(fp) / sum(i8)
    assert ratio > 3.5  # fp32 model: 4x minus the scale-slab overhead
    # the engine's own context carries kv_dtype (verifier parity)
    engine = paged_engine(layer_cfgs, params, kv_dtype="int8")
    ctx = engine._serving_context()
    assert ctx["kv_dtype"] == "int8"
    issues = []
    prof = _serving_kv_profile(layer_cfgs, ctx, issues, "error")
    assert not issues
    attn = [m for m in prof if m > 0]
    assert attn and abs(
        attn[0] - paged_pool_mb(engine.num_pages, engine.page_size,
                                2, 32, kv_dtype="int8")
    ) < 1e-9
    # unknown dtype -> diagnostic; slot context + kv_dtype -> rejected
    bad = []
    assert _serving_kv_profile(
        layer_cfgs, dict(num_pages=12, page_size=8, kv_dtype="int4"),
        bad, "error",
    ) is None and "int4" in bad[0].message
    bad = []
    assert _serving_kv_profile(
        layer_cfgs, dict(slots=2, max_len=32, kv_dtype="int8"),
        bad, "error",
    ) is None and "paged" in bad[0].message
    with pytest.raises(ValueError, match="kv_dtype"):
        paged_engine(layer_cfgs, params, kv_dtype="int4")
    # the decode profiler stamps + charges the same formula
    bench = DecodeModelBenchmarker(
        layer_cfgs, slots=4, max_len=32, num_pages=12, page_size=8,
        kv_dtype="int8",
    )
    assert bench.operating_point["kv_dtype"] == "int8"
    bench_fp = DecodeModelBenchmarker(
        layer_cfgs, slots=4, max_len=32, num_pages=12, page_size=8,
    )
    _, mem_i8 = bench.benchmark()
    _, mem_fp = bench_fp.benchmark()
    attn_idx = [i for i, cfg in enumerate(layer_cfgs)
                if cfg.get("layer_type") == "GptBlock_Attn"]
    for i in attn_idx:
        # same compute profile, pool charged at the quantized width
        assert mem_fp[i] - mem_i8[i] == pytest.approx(
            fp[i] - i8[i]
        )
    with pytest.raises(ValueError, match="paged-pool policy"):
        DecodeModelBenchmarker(layer_cfgs, slots=4, max_len=32,
                               kv_dtype="int8")


# --------------------------------------------------------------------------
# chunked prefill + speculative decoding
# --------------------------------------------------------------------------


def test_chunk_budget_policy_contract():
    """Pure scheduling: the budget defers chunk rows while decode
    exists to protect, opens up when idle, and its starvation bound is
    rows x chunk."""
    from skycomputing_tpu.serving import ChunkBudgetPolicy

    policy = ChunkBudgetPolicy(16, max_chunk_rows=2, idle_chunk_rows=6)
    assert policy.rows_for_tick(pending=0, decoding=5) == 0
    assert policy.rows_for_tick(pending=8, decoding=3) == 2
    assert policy.rows_for_tick(pending=1, decoding=3) == 1
    assert policy.rows_for_tick(pending=8, decoding=0) == 6
    assert policy.rows_for_tick(pending=4, decoding=0) == 4
    assert policy.starvation_bound_tokens() == 32
    with pytest.raises(ValueError):
        ChunkBudgetPolicy(0)
    with pytest.raises(ValueError):
        ChunkBudgetPolicy(16, max_chunk_rows=0)
    with pytest.raises(ValueError):
        ChunkBudgetPolicy(16, max_chunk_rows=4, idle_chunk_rows=2)


def test_chunked_prefill_token_identity(gpt):
    """Chunked prefill is pure scheduling: every output matches the
    one-shot `generate` AND the unchunked engine, with chunk
    waves actually taken and decode interleaved between them."""
    layer_cfgs, params, fwd = gpt
    rng = np.random.default_rng(21)
    specs = [(14, 6), (5, 9), (16, 3), (12, 7), (3, 4), (15, 5)]
    chunked_reqs = mixed_requests(rng, specs)
    plain_reqs = [
        Request(prompt=r.prompt.copy(), max_new_tokens=r.max_new_tokens)
        for r in chunked_reqs
    ]
    chunked = paged_engine(layer_cfgs, params, prefill_batch=2,
                           prefill_chunk=8)
    plain = paged_engine(layer_cfgs, params, prefill_batch=2)
    c_out = chunked.run(chunked_reqs)
    p_out = plain.run(plain_reqs)
    for cr, pr in zip(chunked_reqs, plain_reqs):
        np.testing.assert_array_equal(
            c_out[cr.request_id], reference(fwd, cr)
        )
        np.testing.assert_array_equal(
            c_out[cr.request_id], p_out[pr.request_id]
        )
    assert chunked.stats.prefill_chunks > 0
    # prompts longer than one chunk took several waves
    assert chunked.stats.prefill_chunks > len(specs)
    chunked._pool.check_consistency()


def test_chunked_midprefill_preempt_and_drain(gpt):
    """A mid-watermark request preempts (recompute-only) and drains
    with its stream intact; refcounts stay consistent throughout."""
    layer_cfgs, params, fwd = gpt
    engine = ServingEngine(
        layer_cfgs, params, num_slots=2, max_len=48,
        buckets=(4, 8, 16), kv_layout="paged", page_size=4,
        prefill_batch=1, prefill_chunk=4, max_chunk_rows=1,
    )
    rng = np.random.default_rng(22)
    victim, other = mixed_requests(rng, [(15, 6), (5, 4)])
    engine.submit(victim)
    engine.submit(other)
    engine.step()  # enrolls; victim advances at most one chunk
    assert victim.request_id in engine._prefilling
    assert victim.prefilled_len > 0
    with pytest.raises(ValueError, match="recomputation"):
        engine.preempt(victim.request_id, mode="swap")
    engine.preempt(victim.request_id)
    assert victim.slot is None and victim.prefilled_len == 0
    engine._pool.check_consistency()
    engine.run()
    np.testing.assert_array_equal(victim.output(), reference(fwd, victim))
    np.testing.assert_array_equal(other.output(), reference(fwd, other))
    # drain() evicts mid-prefill requests too (the migration primitive)
    r2 = mixed_requests(rng, [(15, 5)])[0]
    engine.submit(r2)
    engine.step()
    drained = engine.drain()
    assert r2 in drained and not engine.has_work()
    engine._pool.check_consistency()


class _SabotagedDraft:
    """A draft that always proposes the WRONG token (off by one in
    vocab space): every verify tick must reject at the first position,
    exercising the full rollback path while the greedy stream stays
    token-identical by construction."""

    def __init__(self, inner, vocab):
        self._inner = inner
        self._vocab = vocab
        self.num_attn = inner.num_attn
        self.extra_param_mb = inner.extra_param_mb

    def draft_k(self, tokens, slabs, tables, index, reserve, k):
        proposals, slabs = self._inner.draft_k(
            tokens, slabs, tables, index, reserve, k
        )
        return (proposals + 1) % self._vocab, slabs


def test_spec_rejection_rollback_keeps_refcounts_and_identity(gpt):
    """Speculation with a 100%-rejecting draft: every tick drafts k,
    rejects at position 0, truncates the watermark, and commits the
    target's own token — outputs stay exactly the non-speculative
    greedy stream and page refcounts never drift."""
    layer_cfgs, params, fwd = gpt
    engine = paged_engine(layer_cfgs, params, prefill_batch=2,
                          spec_k=2, draft_blocks=1)
    engine._draft = _SabotagedDraft(engine._draft, vocab=512)
    rng = np.random.default_rng(23)
    requests = mixed_requests(rng, [(5, 8), (12, 5), (3, 6), (9, 4)])
    outputs = engine.run(requests)
    for r in requests:
        np.testing.assert_array_equal(
            outputs[r.request_id], reference(fwd, r)
        )
    stats = engine.stats
    assert stats.draft_tokens > 0
    # total rejection: nothing accepted, every verify tick rolled back
    assert stats.accepted_draft_tokens == 0
    assert stats.spec_rollbacks > 0
    engine._pool.check_consistency()


def test_spec_acceptance_commits_multiple_tokens(gpt):
    """With the honest prefix-slice draft, accepted tokens commit in
    bulk: generated tokens exceed verify ticks whenever acceptance
    lands, and identity holds either way."""
    layer_cfgs, params, fwd = gpt
    engine = paged_engine(layer_cfgs, params, prefill_batch=2,
                          spec_k=3, draft_blocks=1)
    rng = np.random.default_rng(24)
    requests = mixed_requests(rng, [(5, 12), (8, 10), (12, 8)])
    outputs = engine.run(requests)
    for r in requests:
        np.testing.assert_array_equal(
            outputs[r.request_id], reference(fwd, r)
        )
    stats = engine.stats
    assert stats.draft_tokens > 0
    assert stats.accepted_draft_tokens >= 0  # model-dependent
    # bookkeeping: every committed token is decode or prefill output
    assert stats.generated_tokens == sum(
        len(r.tokens) for r in requests
    )
    engine._pool.check_consistency()


def zero_tail_residuals(layer_cfgs, params_list, draft_blocks):
    """Zero the residual output projections (``c_proj``) of every
    block at or past ``draft_blocks``, making those blocks exact
    identities.  The prefix-slice draft then agrees with the target at
    EVERY position (accept rate 1.0).  The target still pays its full
    per-layer compute: zeroed matmuls cost the same FLOPs."""
    new = list(params_list)
    block = -1
    for i, cfg in enumerate(layer_cfgs):
        lt = cfg.get("layer_type")
        if lt == "GptBlock_Attn":
            block += 1
        if lt in ("GptBlock_Attn", "GptBlock_Mlp") and \
                block >= draft_blocks:
            layer = dict(new[i])
            layer["c_proj"] = jax.tree_util.tree_map(
                np.zeros_like, layer["c_proj"]
            )
            new[i] = layer
    return new


def test_spec_exact_draft_accept_rate_is_one(gpt):
    """With a PERFECT draft (tail blocks' residual projections zeroed)
    the accept rate reads exactly 1.0
    and no rollback fires — even when generation budgets are not
    multiples of spec_k+1, because the denominator counts only USABLE
    proposals (a final tick's surplus drafts are not failures)."""
    layer_cfgs, params, _ = gpt
    sparams = zero_tail_residuals(layer_cfgs, list(params), 1)
    spec = paged_engine(layer_cfgs, sparams, prefill_batch=2,
                        spec_k=3, draft_blocks=1)
    plain = paged_engine(layer_cfgs, sparams, prefill_batch=2)
    rng = np.random.default_rng(28)
    # budgets 6 and 9: both hit the remaining-cap tick (6 = 4+2,
    # 9 = 4+4+1 under spec_k=3)
    spec_reqs = mixed_requests(rng, [(5, 6), (8, 9)])
    plain_reqs = [
        Request(prompt=r.prompt.copy(), max_new_tokens=r.max_new_tokens)
        for r in spec_reqs
    ]
    s_out = spec.run(spec_reqs)
    p_out = plain.run(plain_reqs)
    for sr, pr in zip(spec_reqs, plain_reqs):
        np.testing.assert_array_equal(
            s_out[sr.request_id], p_out[pr.request_id]
        )
    stats = spec.stats
    assert stats.draft_tokens > 0
    assert stats.accepted_draft_tokens == stats.draft_tokens
    assert stats.spec_rollbacks == 0


def test_spec_sampling_rows_keep_streams_and_counters_clean(gpt):
    """Temperature rows under speculation: the sample stream is
    identical to the non-speculative engine's (`fold_in(seed, pos)` is
    position-keyed, and the verify's position-0 logits ARE the decode
    logits), and an all-sampling batch falls back to the plain decode
    tick — no drafts burned, no accept-rate pollution."""
    layer_cfgs, params, _ = gpt
    rng = np.random.default_rng(27)
    prompt = rng.integers(1, 512, (7,)).astype(np.int32)
    spec = paged_engine(layer_cfgs, params, spec_k=2, draft_blocks=1)
    plain = paged_engine(layer_cfgs, params)
    r_spec = Request(prompt=prompt.copy(), max_new_tokens=6,
                     temperature=0.8, seed=5)
    r_plain = Request(prompt=prompt.copy(), max_new_tokens=6,
                      temperature=0.8, seed=5)
    o_spec = spec.run([r_spec])[r_spec.request_id]
    o_plain = plain.run([r_plain])[r_plain.request_id]
    np.testing.assert_array_equal(o_spec, o_plain)
    # the all-sampling tick fell back: sampling consumed zero drafts
    assert spec.stats.draft_tokens == 0
    assert spec.stats.spec_rollbacks == 0


def test_chunk_spec_zero_steady_state_recompiles(gpt):
    """With chunking AND speculation live, one warmup pass compiles
    every program (bucket prefills reused by chunk waves, draft Lq=1,
    verify Lq=k+1) and the steady state pins ZERO XLA compiles."""
    layer_cfgs, params, fwd = gpt
    engine = ServingEngine(
        layer_cfgs, params, num_slots=3, max_len=48, buckets=(8, 16),
        kv_layout="paged", page_size=8, prefill_batch=2,
        prefill_chunk=8, spec_k=2, draft_blocks=1,
    )
    rng = np.random.default_rng(25)
    # warmup: every bucket + chunked multi-wave prefill + spec ticks
    engine.run(mixed_requests(rng, [(5, 4), (14, 4), (11, 3)]))
    warm = xla_compile_count()
    wave = mixed_requests(
        rng, [(6, 8), (2, 3), (15, 5), (9, 4), (13, 6)]
    )
    outputs = engine.run(wave)
    assert xla_compile_count() == warm, (
        "chunked+speculative steady state recompiled"
    )
    for r in wave:
        np.testing.assert_array_equal(
            outputs[r.request_id], reference(fwd, r)
        )


def test_chunk_spec_reconfigure_verify_then_apply(gpt):
    """The chunk/spec knobs ride reconfigure's verify-then-apply: an
    off-bucket chunk or a malformed spec_k is rejected with the engine
    untouched; enable/disable apply cleanly with live requests, and
    disabling chunking re-queues mid-watermark requests instead of
    stranding them."""
    from skycomputing_tpu.analysis.plan_check import PlanError

    layer_cfgs, params, fwd = gpt
    engine = paged_engine(layer_cfgs, params, prefill_batch=2,
                          draft_blocks=1)
    rng = np.random.default_rng(26)
    requests = mixed_requests(rng, [(5, 10), (12, 8)])
    for r in requests:
        engine.submit(r)
    for _ in range(2):
        engine.step()
    # rejections: engine exactly as it was
    with pytest.raises(PlanError, match="prefill_chunk"):
        engine.reconfigure(prefill_chunk=5)  # not a bucket
    assert engine.prefill_chunk is None
    with pytest.raises(PlanError, match="spec_k"):
        engine.reconfigure(spec_k=-1)
    assert engine.spec_k == 0
    no_draft = paged_engine(layer_cfgs, params)
    with pytest.raises(ValueError, match="draft_blocks"):
        no_draft.reconfigure(spec_k=2)
    assert no_draft.spec_k == 0 and no_draft._draft is None
    # a rows knob with chunking off fails loudly (constructor parity),
    # never silently dropping the operator's starvation bound
    with pytest.raises(ValueError, match="requires prefill_chunk"):
        engine.reconfigure(max_chunk_rows=4)
    # apply: enable both, keep serving, disable both, keep serving
    engine.reconfigure(prefill_chunk=8, spec_k=2)
    assert engine.prefill_chunk == 8 and engine.spec_k == 2
    assert engine._draft is not None
    more = mixed_requests(rng, [(14, 6), (6, 5)])
    for r in more:
        engine.submit(r)
    engine.step()  # may hold a mid-watermark request
    engine.reconfigure(prefill_chunk=0, spec_k=0)
    assert engine.prefill_chunk is None and engine.spec_k == 0
    assert not engine._prefilling  # nothing stranded mid-watermark
    engine.run()
    for r in requests + more:
        np.testing.assert_array_equal(r.output(), reference(fwd, r))
    engine._pool.check_consistency()


def test_chunk_tick_is_fair_and_counts_real_deferrals(gpt):
    """One tick gives each mid-prefill request AT MOST one chunk (the
    head can never eat the budget while later enrollees starve), and
    `chunk_stalls` counts only ticks that actually deferred someone —
    a lone request chunking through its prompt is not a stall."""
    layer_cfgs, params, fwd = gpt
    # lone request: 4 chunk ticks, zero stalls
    solo = ServingEngine(
        layer_cfgs, params, num_slots=2, max_len=48, buckets=(4, 16),
        kv_layout="paged", page_size=4, prefill_batch=1,
        prefill_chunk=4, max_chunk_rows=1,
    )
    r = mixed_requests(np.random.default_rng(30), [(15, 3)])[0]
    solo.run([r])
    assert solo.stats.prefill_chunks >= 3
    assert solo.stats.chunk_stalls == 0
    np.testing.assert_array_equal(r.output(), reference(fwd, r))
    # two enrollees, prefill_batch=1 so each wave holds one request:
    # a budget of 2 must advance BOTH every tick (head first, then the
    # next un-advanced enrollee) — never the head twice
    pair = ServingEngine(
        layer_cfgs, params, num_slots=3, max_len=48, buckets=(4, 16),
        kv_layout="paged", page_size=4, prefill_batch=1,
        prefill_chunk=4, max_chunk_rows=2,
    )
    rng = np.random.default_rng(31)
    a, b = mixed_requests(rng, [(15, 3), (14, 3)])
    pair.submit(a)
    pair.submit(b)
    pair.step()  # both enroll; both must advance exactly one chunk
    assert a.request_id in pair._prefilling
    assert b.request_id in pair._prefilling
    assert a.prefilled_len == 4 and b.prefilled_len == 4
    pair.run()
    np.testing.assert_array_equal(a.output(), reference(fwd, a))
    np.testing.assert_array_equal(b.output(), reference(fwd, b))


def test_reconfigure_spec_enable_charges_draft_memory(gpt, devices):
    """Enabling speculation via reconfigure makes the draft's LM-head
    copy newly resident on stage 0 — the verify-then-apply pre-flight
    must charge it BEFORE the device_put, so a budget that fits the
    slabs but not the draft rejects cleanly with the engine untouched."""
    from skycomputing_tpu.analysis.plan_check import PlanError
    from skycomputing_tpu.dynamics import WorkerManager

    layer_cfgs, params, _ = gpt

    def build(limit0):
        wm = WorkerManager()
        wm.load_worker_pool_from_config([
            dict(name=f"n{i}", device_config=dict(device_index=i),
                 extra_config=dict(mem_limit=limit))
            for i, limit in enumerate((limit0, 10_000.0))
        ])
        cursor = 0
        for w, c in zip(wm.worker_pool, [3, 3]):
            w.model_config = layer_cfgs[cursor:cursor + c]
            w.order = w.rank + 1
            cursor += c
        return ServingEngine(
            layer_cfgs, params, num_slots=2, max_len=32, buckets=(8,),
            worker_manager=wm, devices=devices, kv_layout="paged",
            page_size=8, draft_blocks=1,
        )

    # stage 0 fits slabs+model (~0.71 MB) but NOT the ~0.13 MB head
    # copy the spec enable would add
    engine = build(limit0=0.78)
    assert engine._pending_draft_mb() > 0.1
    with pytest.raises(PlanError, match="speculative draft"):
        engine.reconfigure(spec_k=2)
    assert engine.spec_k == 0 and engine._draft is None
    # with headroom the same enable applies and stamps the charge
    roomy = build(limit0=10_000.0)
    roomy.reconfigure(spec_k=2)
    assert roomy.spec_k == 2 and roomy._draft is not None
    assert roomy._draft_mb == pytest.approx(
        roomy._draft.extra_param_mb
    )


def test_spec_preflight_charges_draft_memory():
    """The knob schema validates prefill_chunk/spec_k, and a serving
    context's draft_mb reaches the memory verifier."""
    from skycomputing_tpu.analysis.plan_check import verify_tuning_knobs

    report = verify_tuning_knobs(buckets=(8, 16), max_len=48,
                                 prefill_chunk=8, spec_k=3)
    assert not report.errors
    report = verify_tuning_knobs(buckets=(8, 16), max_len=48,
                                 prefill_chunk=12)
    assert any("prefill_chunk" in i.message for i in report.errors)
    report = verify_tuning_knobs(spec_k=-2)
    assert any("spec_k" in i.message for i in report.errors)
    report = verify_tuning_knobs(max_len=4, spec_k=8)
    assert any("verify window" in i.message for i in report.errors)
