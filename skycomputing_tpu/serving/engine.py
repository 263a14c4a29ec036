"""ServingEngine: iteration-level continuous batching over pipeline stages.

The training engine (``parallel/pipeline.py``) amortizes host dispatch
over microbatches; serving has no microbatches — it has *requests* that
arrive whenever they arrive and finish whenever they finish.  The two
techniques that make a pipeline throughput-competitive for serving, both
implemented here:

- **continuous batching** (Orca, OSDI '22): scheduling happens at
  *decode-iteration* granularity.  Every tick the engine (1) admits
  queued requests onto free decode rows via a bucketed prefill wave and
  (2) runs ONE single-token decode step over all rows.  A finishing
  request frees its row between ticks; a joining request occupies one
  between ticks; the running batch never drains to accommodate either.
- **a paged KV cache** (PagedAttention, SOSP '23 + SGLang-style radix
  prefix caching): per-stage ``[num_pages, page_size, heads *
  head_dim]`` page pools addressed through per-request page tables
  (host bookkeeping — free-list allocator, refcounts, copy-on-write
  prefix sharing, radix index, swap-preemption — in
  ``serving/paging.py``), so admission charges a request its TRUE
  footprint in pages and concurrency floats with memory.  The decode
  path picks its attention body per engine (``attn_impl``: the fused
  Pallas kernel on TPU, the XLA gather+softmax reference elsewhere),
  bounds each step's page-table width to the wave's live span, and can
  store pages int8 with per-page-per-head scale slabs
  (``kv_dtype="int8"`` — ~4x pages per MB at fp32 model dtype, bounded
  error; see docs/serving.md).  Every compiled program keeps a fixed
  shape regardless of which requests are live: one step program per
  (bucket, table width) pair, warmed like prefill buckets
  (``serving/batcher.py``); after warmup the steady state is
  zero-recompile, pinned by ``xla_compile_count()`` in
  ``tests/test_serving.py``.

Pipeline integration: stages come from the same worker-manager
allocation the MPMD trainer uses (``Allocator.serving_allocate``
balances them against *decode-step* costs — see ``serving/profile.py``),
each stage's params and slabs are committed to its device, and
inter-stage hidden-state/index hops ride ``device_put_elided`` so
same-device handoffs are free and cross-device ones batch into one put.

Inactive rows ride through the decode step computing masked garbage —
that waste is the price of a fixed shape, and ``ServingStats.
batch_occupancy`` makes it visible instead of hidden.
"""

from __future__ import annotations

import hashlib
import json
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..builder import build_layer_stack
from ..models.gpt import (
    GptEmbeddings,
    _gcfg,
    apply_kv_paged,
    attn_indices,
    decode_modules,
    draft_slice_indices,
)
from ..parallel.pipeline import (
    _donation_enabled,
    device_put_elided,
    xla_compile_count,
)
from ..telemetry import (
    LiveMetricsMixin,
    MetricsRegistry,
    get_tracer,
    span_sinks,
)
from .batcher import (
    AdmissionQueue,
    FAILED,
    FINISHED,
    QueueFullError,
    REJECTED,
    RUNNING,
    Request,
    ShapeBucketer,
)
from .kv_cache import (
    QuantizedPages,
    init_paged_caches,
    kv_spec_from_config,
)
from .paging import (
    ChunkBudgetPolicy,
    PagedKVCachePool,
    RowAllocator,
    choose_preempt_mode,
    pages_for,
)
from .speculative import (
    DraftModel,
    greedy_accept_count,
    tree_param_mb,
)


# one compiled gather/argmax pair per (batch, vocab) shape — module-level
# jits so every engine instance shares the executables
_gather_last = jax.jit(
    lambda logits, pos: logits[jnp.arange(logits.shape[0]), pos, :]
)
_argmax_tokens = jax.jit(
    lambda logits: jnp.argmax(logits, axis=-1).astype(jnp.int32)
)

# Process-level stage-program cache: the jit'd step closures, keyed by
# the stage's layer-config signature (+ max_len + donation).
# jax's compilation cache is keyed by FUNCTION IDENTITY, so two engines
# built from identical configs would otherwise re-trace and re-compile
# every program — which makes a fleet replica's re-form pay the full
# compile bill on the serving path.  Reusing the closure lets a
# re-formed replica (and every same-config engine in tests/benches)
# restart at cache-hit speed, the serving twin of the training side's
# persistent-compile-cache-into-relaunched-trainer idea.  Safe because
# the closures are pure functions of their arguments: modules are
# stateless config-built definitions (params always passed in), and the
# signature pins the exact config that built them.
_STAGE_PROGRAMS: Dict[str, Any] = {}


@dataclass
class ServingStats:
    """SLO accounting for a :class:`ServingEngine` (the serving
    counterpart of ``PipelineStats``).

    Counters are cumulative since engine construction; ``queue_depth``
    and ``batch_occupancy`` are gauges from the last iteration.
    ``compiles`` counts XLA backend compiles observed during engine
    calls — after bucket warmup it must stop moving (the steady-state
    zero-recompile contract).  ``queue_stalls`` counts iterations where
    admission wanted a row or pages and none were free (the
    pool-exhaustion queueing path); ``preemptions`` counts evictions
    (the request re-queues and resumes by recomputation or swap-in).
    """

    iterations: int = 0
    prefill_waves: int = 0
    prefill_tokens: int = 0
    decode_tokens: int = 0
    generated_tokens: int = 0
    admitted: int = 0
    finished: int = 0
    preemptions: int = 0
    queue_stalls: int = 0
    # bounded-admission accounting: submissions refused (policy
    # "reject") or displaced (policy "shed") by a full queue — load
    # shedding is only acceptable when it is visible
    queue_rejections: int = 0
    compiles: int = 0
    # page-pool accounting:
    # prefix_hits/prefix_tokens_reused measure the radix cache
    # (prefill compute NOT spent), cow_copies the partial-page clones
    # that keep shared pages read-only, swap_outs/swap_ins the
    # host-pool preemption path, prefix_evictions the LRU pressure
    prefix_hits: int = 0
    prefix_tokens_reused: int = 0
    cow_copies: int = 0
    swap_outs: int = 0
    swap_ins: int = 0
    # swap records whose swap-in checksum verification failed: the
    # record is dropped and the victim resumes by recompute-from-prompt
    # instead of restoring poisoned KV — corruption is only acceptable
    # when it is caught, counted, and survived
    swap_corruptions: int = 0
    prefix_evictions: int = 0
    # chunked-prefill accounting (prefill_chunk set): prefill_chunks
    # counts chunk rows computed (one request-chunk each);
    # chunk_stalls counts ticks where pending chunk work was deferred
    # by the decode-protecting budget — sustained growth means prefill
    # demand exceeds the interleave budget (raise max_chunk_rows or
    # prefill_chunk, or accept the TTFT cost)
    prefill_chunks: int = 0
    chunk_stalls: int = 0
    # int8-KV accounting (kv_dtype="int8"): quantized_pages counts
    # page-tile quantization events (every page a write wave touched
    # re-quantizes through its scale — write amplification made
    # visible); dequant_blocks counts page blocks dequantized by
    # attention reads (active rows x gathered table width per step —
    # the work the bounded gather and the fused kernel shrink)
    quantized_pages: int = 0
    dequant_blocks: int = 0
    # paged-attention accounting, one target forward per paged decode
    # tick: attn_pages_live = pages inside the rows' causal bounds
    # (sum over the program's rows of ceil((index + Lq) / page_size),
    # an idle row's one page included) — what the fused kernel fetches
    # and steps through; attn_pages_table = rows x table width — what
    # the XLA reference gathers.  Their ratio is the share of the
    # table the kernel walks
    attn_pages_live: int = 0
    attn_pages_table: int = 0
    # speculative-decoding accounting (spec_k > 0): draft_tokens =
    # USABLE draft proposals (capped at each row's remaining token
    # budget — surplus drafts a row could never commit don't deflate
    # the rate), accepted_draft_tokens committed after the target's
    # verify forward agreed, spec_rollbacks = verify outcomes that
    # truncated a row's watermark past written speculative KV
    # (accepted_draft_tokens / draft_tokens is the live accept rate
    # the speculation speedup rides on; exactly 1.0 for a perfect
    # draft)
    draft_tokens: int = 0
    accepted_draft_tokens: int = 0
    spec_rollbacks: int = 0
    # disaggregated-serving accounting (the prefill/decode handoff
    # plane, docs/disagg.md): handoffs_out counts swap records exported
    # as portable handoffs, handoffs_in records seated for swap-in
    # resume on this engine, handoff_failures records refused at the
    # import checksum gate (the request recomputes from its prompt —
    # counted, never lost), handoff_bytes the host payload moved
    handoffs_out: int = 0
    handoffs_in: int = 0
    handoff_failures: int = 0
    handoff_bytes: int = 0
    # gauges
    queue_depth: int = 0
    batch_occupancy: float = 0.0
    pages_in_use: int = 0
    free_pages: int = 0
    # blocked wall time per phase (timed across block_until_ready)
    prefill_s: float = 0.0
    decode_s: float = 0.0
    # per-request SLO samples
    ttft_s: List[float] = field(default_factory=list)
    tpot_s: List[float] = field(default_factory=list)

    #: metric classification (telemetry.MetricsRegistry contract):
    #: counters are cumulative for the ENGINE's lifetime and never
    #: reset — ``reconfigure()`` preserves this object, and a fleet
    #: replica's re-form (a new engine = a new lifetime) is bridged by
    #: ``EngineReplica.stats_snapshot`` carrying the prior generations'
    #: totals, so time-series rate derivation stays well-defined.
    #: Covers ``snapshot()`` keys, derived fields included.
    FIELD_TYPES = {
        "iterations": "counter", "prefill_waves": "counter",
        "prefill_tokens": "counter", "decode_tokens": "counter",
        "generated_tokens": "counter", "admitted": "counter",
        "finished": "counter", "preemptions": "counter",
        "queue_stalls": "counter", "queue_rejections": "counter",
        "compiles": "counter", "prefill_s": "counter",
        "decode_s": "counter",
        "prefix_hits": "counter", "prefix_tokens_reused": "counter",
        "cow_copies": "counter", "swap_outs": "counter",
        "swap_ins": "counter", "swap_corruptions": "counter",
        "prefix_evictions": "counter",
        "prefill_chunks": "counter", "chunk_stalls": "counter",
        "quantized_pages": "counter", "dequant_blocks": "counter",
        "attn_pages_live": "counter", "attn_pages_table": "counter",
        "draft_tokens": "counter",
        "accepted_draft_tokens": "counter",
        "spec_rollbacks": "counter",
        "handoffs_out": "counter", "handoffs_in": "counter",
        "handoff_failures": "counter", "handoff_bytes": "counter",
        "queue_depth": "gauge", "batch_occupancy": "gauge",
        "pages_in_use": "gauge", "free_pages": "gauge",
        "tokens_per_s": "gauge",
        "ttft_p50_s": "gauge", "ttft_p95_s": "gauge",
        "tpot_p50_s": "gauge", "tpot_p95_s": "gauge",
    }

    #: the cumulative subset a replica carries across re-forms
    COUNTER_FIELDS = tuple(
        k for k, v in FIELD_TYPES.items()
        if v == "counter"
    )

    def tokens_per_s(self) -> float:
        """Generated tokens per second of engine compute wall clock."""
        elapsed = self.prefill_s + self.decode_s
        return self.generated_tokens / elapsed if elapsed > 0 else 0.0

    def snapshot(self) -> Dict[str, Any]:
        """JSON-able summary (percentiles over the SLO samples)."""
        def pct(xs, q):
            return float(np.percentile(xs, q)) if xs else None

        return dict(
            iterations=self.iterations,
            prefill_waves=self.prefill_waves,
            prefill_tokens=self.prefill_tokens,
            decode_tokens=self.decode_tokens,
            generated_tokens=self.generated_tokens,
            admitted=self.admitted,
            finished=self.finished,
            preemptions=self.preemptions,
            queue_stalls=self.queue_stalls,
            queue_rejections=self.queue_rejections,
            compiles=self.compiles,
            prefix_hits=self.prefix_hits,
            prefix_tokens_reused=self.prefix_tokens_reused,
            cow_copies=self.cow_copies,
            swap_outs=self.swap_outs,
            swap_ins=self.swap_ins,
            swap_corruptions=self.swap_corruptions,
            prefix_evictions=self.prefix_evictions,
            prefill_chunks=self.prefill_chunks,
            chunk_stalls=self.chunk_stalls,
            quantized_pages=self.quantized_pages,
            dequant_blocks=self.dequant_blocks,
            attn_pages_live=self.attn_pages_live,
            attn_pages_table=self.attn_pages_table,
            draft_tokens=self.draft_tokens,
            accepted_draft_tokens=self.accepted_draft_tokens,
            spec_rollbacks=self.spec_rollbacks,
            handoffs_out=self.handoffs_out,
            handoffs_in=self.handoffs_in,
            handoff_failures=self.handoff_failures,
            handoff_bytes=self.handoff_bytes,
            queue_depth=self.queue_depth,
            batch_occupancy=self.batch_occupancy,
            pages_in_use=self.pages_in_use,
            free_pages=self.free_pages,
            prefill_s=self.prefill_s,
            decode_s=self.decode_s,
            tokens_per_s=self.tokens_per_s(),
            ttft_p50_s=pct(self.ttft_s, 50),
            ttft_p95_s=pct(self.ttft_s, 95),
            tpot_p50_s=pct(self.tpot_s, 50),
            tpot_p95_s=pct(self.tpot_s, 95),
        )


# small page-slab utilities, module-level jits so every engine shares
# the executables (shape-keyed: one compile per slab geometry).
# _copy_page is undonated, so on accelerators each COW event pays a
# slab-sized copy; COW fires at most once per prefix-hit admission, so
# this is off the per-token path — donate + rebind if it ever shows up
_copy_page = jax.jit(lambda slab, src, dst: slab.at[dst].set(slab[src]))
_gather_rows = jax.jit(
    lambda slab, table: slab[jnp.clip(table, 0, slab.shape[0] - 1)]
)
_scatter_rows = jax.jit(
    lambda slab, table, vals: slab.at[table].set(
        vals.astype(slab.dtype), mode="drop"
    )
)


class _ServingStage:
    """One pipeline stage: module slice + device + per-attention-layer
    page slabs ``[num_pages, page_size, heads * head_dim]`` + the one
    fused step program (prefill and decode are the same function at
    different input shapes — see ``models/gpt.apply_kv_paged``)."""

    def __init__(
        self,
        stage_index: int,
        modules: Sequence[Any],
        params: Sequence[Any],
        device,
        num_pages: int,
        page_size: int,
        program_key: Optional[str] = None,
        kv_dtype: Optional[str] = None,
        attn_impl: str = "xla",
    ):
        self.stage_index = stage_index
        self.modules = list(modules)
        self.device = device
        # trace-lane name, same convention as StageRuntime.lane_name so
        # serving and training timelines read identically in Perfetto
        self.lane_name = f"stage {stage_index} [{device}]"
        self.params: List[Any] = jax.device_put(list(params), device)
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.kv_dtype = kv_dtype
        self.attn_impl = attn_impl
        self.specs = [
            kv_spec_from_config(
                _gcfg(self.modules[i].config).to_dict(), page_size
            )
            for i in attn_indices(self.modules)
        ]
        self.slabs = self.build_slabs(num_pages, page_size)
        cached = (
            _STAGE_PROGRAMS.get(program_key)
            if program_key is not None else None
        )
        if cached is not None:
            # same config signature -> the closure (and jax's traced/
            # compiled cache behind its identity) is reusable as-is
            self._step_donated = cached
            return
        mods = self.modules
        impl = attn_impl

        def step(params_list, data, slabs, tables, index, valid_len):
            return apply_kv_paged(
                mods, params_list, data, slabs, tables, index,
                valid_len, attn_impl=impl,
            )

        # convention: the *_donated handle consumes its slabs on call —
        # the engine rebinds st.slabs to the outputs on the same line.
        # Donation follows the backend like the training engine:
        # in-place slab reuse pays on TPU/GPU, is inert on CPU.
        if _donation_enabled():
            self._step_donated = jax.jit(step, donate_argnums=(2,))
        else:
            self._step_donated = jax.jit(step)
        if program_key is not None:
            _STAGE_PROGRAMS[program_key] = self._step_donated

    def build_slabs(self, num_pages: int, page_size: int):
        """Fresh zeroed page slabs (construction + the reconfigure
        pre-build, so an allocation failure surfaces while the engine
        is still intact).  ``kv_dtype="int8"`` builds QuantizedPages
        pairs: int8 values + the parallel float32 scale slabs."""
        return init_paged_caches(
            self.specs, num_pages, page_size, device=self.device,
            kv_dtype=self.kv_dtype,
        )

    def apply_cow_plan(self, plan) -> None:
        """Execute the pool's copy-on-write plan
        (``PagedKVCachePool.cow_plan``) against this stage's slabs —
        the plan, not this method, is the source of truth for WHAT a
        clone copies: on an int8 pool it names the scale row alongside
        the values (a cloned page dequantized with the donor's scale
        but re-scaled under its new owner would corrupt the shared
        prefix).  A plan/slab mismatch — a scale copy planned for a
        pool whose slabs are not quantized, or vice versa — raises:
        that is kv_dtype drift between the allocator and the device
        slabs, never something to paper over."""
        copies: Dict[str, Any] = {}
        for kind, src, dst in plan:
            if kind not in ("values", "scales"):
                raise ValueError(f"unknown COW plan entry {kind!r}")
            copies[kind] = (np.int32(src), np.int32(dst))
        if not copies:
            return

        def cp(slab):
            quantized = isinstance(slab, QuantizedPages)
            if "scales" in copies and not quantized:
                raise ValueError(
                    "COW plan names a scale copy but this stage's "
                    "slabs are not quantized — pool/stage kv_dtype "
                    "drift"
                )
            if not quantized:
                s, d = copies["values"]
                return _copy_page(slab, s, d)
            values, scale = slab.values, slab.scale
            if "values" in copies:
                s, d = copies["values"]
                values = _copy_page(values, s, d)
            if "scales" in copies:
                s, d = copies["scales"]
                scale = _copy_page(scale, s, d)
            return QuantizedPages(values, scale)

        # one pass over the slab list regardless of how many entry
        # kinds the plan carries (values + scales copy together)
        self.slabs = [(cp(k), cp(v)) for k, v in self.slabs]

    def swap_out(self, table: np.ndarray) -> List[Any]:
        """Host copies of the pages in ``table`` (sentinel-padded, so
        the gathered shape is fixed at [max_pages, page_size, ...] and
        compiles once); sentinel rows carry garbage the swap-in scatter
        drops.  int8 slabs swap their scale rows alongside the values —
        a page restored without its scale would dequantize garbage."""
        t = jnp.asarray(table, jnp.int32)

        def g(slab):
            if isinstance(slab, QuantizedPages):
                return QuantizedPages(
                    np.asarray(_gather_rows(slab.values, t)),
                    np.asarray(_gather_rows(slab.scale, t)),
                )
            return np.asarray(_gather_rows(slab, t))

        return [(g(k), g(v)) for k, v in self.slabs]

    def swap_in(self, table: np.ndarray, host_pairs: List[Any]) -> None:
        """Scatter host page copies back into fresh pages (sentinel
        table rows drop)."""
        t = jnp.asarray(table, jnp.int32)

        def s(slab, host):
            if isinstance(slab, QuantizedPages):
                return QuantizedPages(
                    _scatter_rows(slab.values, t,
                                  jnp.asarray(host.values)),
                    _scatter_rows(slab.scale, t,
                                  jnp.asarray(host.scale)),
                )
            return _scatter_rows(slab, t, jnp.asarray(host))

        self.slabs = [
            (s(k, hk), s(v, hv))
            for (k, v), (hk, hv) in zip(self.slabs, host_pairs)
        ]


def _swap_record_checksum(pages: int, index: int,
                          data: List[Any]) -> str:
    """sha256 over a swap record's host payload (page count, resume
    index, and every host array byte — int8 records hash their scale
    rows alongside the values, since a page restored under the wrong
    scale dequantizes garbage just as surely as flipped value bits).
    Stamped at swap-out, verified at swap-in: the integrity half of
    the host-pool preemption path."""
    h = hashlib.sha256()
    h.update(f"{int(pages)}:{int(index)}".encode())

    def fold(host) -> None:
        # data nests: stages -> per-layer (k, v) pairs -> arrays or
        # QuantizedPages (values + scale) — recurse to the leaves
        if isinstance(host, QuantizedPages):
            fold(host.values)
            fold(host.scale)
            return
        if isinstance(host, (list, tuple)):
            for item in host:
                fold(item)
            return
        h.update(np.ascontiguousarray(host).tobytes())

    fold(data)
    return h.hexdigest()


def _swap_record_nbytes(data: List[Any]) -> int:
    """Total host bytes a swap record parks (the payload a handoff
    moves between pools — ``handoff_bytes`` accounting)."""
    total = 0

    def fold(host) -> None:
        nonlocal total
        if isinstance(host, QuantizedPages):
            fold(host.values)
            fold(host.scale)
            return
        if isinstance(host, (list, tuple)):
            for item in host:
                fold(item)
            return
        total += int(np.ascontiguousarray(host).nbytes)

    fold(data)
    return total


def _stage_slab_checksums(data: List[Any]) -> List[str]:
    """One sha256 per stage's host slabs (same leaf fold as
    ``_swap_record_checksum``) — a corrupted handoff names the stage
    instead of just failing the whole record."""
    out = []
    for stage_pairs in data:
        h = hashlib.sha256()

        def fold(host) -> None:
            if isinstance(host, QuantizedPages):
                fold(host.values)
                fold(host.scale)
                return
            if isinstance(host, (list, tuple)):
                for item in host:
                    fold(item)
                return
            h.update(np.ascontiguousarray(host).tobytes())

        fold(stage_pairs)
        out.append(h.hexdigest())
    return out


class ServingEngine(LiveMetricsMixin):
    """Continuous-batching GPT serving over allocator-placed stages.

    ``model_cfg`` is the same layer-config list every other subsystem
    speaks (``gpt_layer_configs`` output); ``params_list`` the matching
    per-layer param trees (``LayerStack.init`` result or
    ``ParameterServer.get_layer_slice(0, n)``).  Stage placement comes
    from ``worker_manager`` (an allocator-written pool, serving-balanced
    via ``Allocator.serving_allocate``) or an explicit ``partition`` of
    layer counts; default is one stage on the first device.
    """

    def __init__(
        self,
        model_cfg: Sequence[Dict],
        params_list: Sequence[Any],
        *,
        num_slots: int = 4,
        max_len: int = 128,
        buckets: Sequence[int] = (16, 32, 64),
        prefill_batch: int = 1,
        max_queue: Optional[int] = None,
        queue_policy: str = "reject",
        pad_id: int = 0,
        worker_manager=None,
        partition: Optional[Sequence[int]] = None,
        devices: Optional[Sequence[Any]] = None,
        preflight: bool = True,
        kv_layout: str = "paged",
        page_size: int = 16,
        num_pages: Optional[int] = None,
        max_pages_per_request: Optional[int] = None,
        max_concurrency: Optional[int] = None,
        enable_prefix_cache: bool = True,
        max_prefix_entries: int = 256,
        preempt_policy: str = "auto",
        prefill_chunk: Optional[int] = None,
        max_chunk_rows: Optional[int] = None,
        spec_k: int = 0,
        draft_blocks: Optional[int] = None,
        kv_dtype: Optional[str] = None,
        attn_impl: Optional[str] = None,
    ):
        if kv_layout != "paged":
            raise ValueError(
                f"kv_layout must be 'paged', got {kv_layout!r}: the "
                f"slot layout was removed, every engine serves from "
                f"the page pool"
            )
        if preempt_policy not in ("auto", "recompute", "swap"):
            raise ValueError(
                f"preempt_policy must be 'auto', 'recompute' or 'swap', "
                f"got {preempt_policy!r}"
            )
        # --- the kernel/quantization operating point ------------------
        # kv_dtype: None keeps the model dtype; "int8" stores pages
        # quantized (per-page-per-head scale slabs, quantize-on-write)
        # — construction state like draft_blocks, NOT a reconfigure
        # knob: a dtype flip would have to re-encode every live page.
        # attn_impl: None auto-detects — the fused Pallas kernel on a
        # TPU backend, the XLA reference elsewhere (interpret-mode
        # Pallas is available everywhere but is a correctness surface,
        # ~orders slower than XLA on CPU; pass "pallas" explicitly to
        # use it off-TPU).
        if kv_dtype not in (None, "int8"):
            raise ValueError(
                f"kv_dtype must be None (model dtype) or 'int8', "
                f"got {kv_dtype!r}"
            )
        if attn_impl not in (None, "xla", "pallas"):
            raise ValueError(
                f"attn_impl must be None (auto), 'xla' or 'pallas', "
                f"got {attn_impl!r}"
            )
        self.kv_dtype = kv_dtype
        self.attn_impl = attn_impl or (
            "pallas" if jax.default_backend() == "tpu" else "xla"
        )
        modules = decode_modules(build_layer_stack(list(model_cfg)))
        if not attn_indices(modules) or not isinstance(
            modules[0], GptEmbeddings
        ):
            raise ValueError(
                "expected a GPT stack: GptEmbeddings + GptBlock_Attn units"
            )
        max_pos = _gcfg(modules[0].config).max_position_embeddings
        # the operating point: max_len becomes the PER-REQUEST virtual
        # span (max_pages_per_request x page_size), and the pool depth
        # decouples from it entirely — num_pages defaults to num_slots
        # whole spans (num_slots x pages_for(max_len)), so num_slots
        # sizes the pool when num_pages is not given
        self.page_size = int(page_size)
        if self.page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if max_pages_per_request is not None:
            self.max_pages_per_request = int(max_pages_per_request)
        else:
            # derived default: cover max_len, but never let the
            # page-rounded span outgrow the model's position table — a
            # max_len the model accepts must not be rejected by its own
            # rounding to pages
            derived = pages_for(max_len, self.page_size)
            if derived * self.page_size > max_pos:
                derived = max_pos // self.page_size
            if derived < 1:
                raise ValueError(
                    f"page_size={self.page_size} exceeds "
                    f"max_position_embeddings={max_pos}"
                )
            self.max_pages_per_request = derived
        max_len = self.max_pages_per_request * self.page_size
        self.num_pages = (
            int(num_pages) if num_pages is not None
            else int(num_slots) * pages_for(max_len, self.page_size)
        )
        self.max_concurrency = (
            int(max_concurrency) if max_concurrency is not None
            else min(self.num_pages, int(num_slots) * 4)
        )
        if self.max_concurrency < 1:
            raise ValueError(
                f"max_concurrency must be >= 1, "
                f"got {self.max_concurrency}"
            )
        # decode rows are the concurrency lanes: num_slots becomes the
        # row count, which is what the fleet's slot-accounting, router
        # load estimates and chaos slot leaks read
        num_slots = self.max_concurrency
        if max_len > max_pos:
            raise ValueError(
                f"max_len={max_len} exceeds "
                f"max_position_embeddings={max_pos}"
            )
        self.bucketer = ShapeBucketer(buckets)
        if self.bucketer.max_bucket > max_len:
            raise ValueError(
                f"largest bucket {self.bucketer.max_bucket} exceeds "
                f"max_len={max_len}"
            )
        self.num_slots = int(num_slots)
        self.max_len = int(max_len)
        self.pad_id = int(pad_id)
        self.enable_prefix_cache = bool(enable_prefix_cache)
        self.preempt_policy = preempt_policy
        self._max_prefix_entries = int(max_prefix_entries)
        if queue_policy not in ("reject", "shed"):
            raise ValueError(
                f"queue_policy must be 'reject' or 'shed', "
                f"got {queue_policy!r}"
            )
        self.max_queue = None if max_queue is None else int(max_queue)
        self.queue_policy = queue_policy
        self._queue = AdmissionQueue(
            self.bucketer, prefill_batch=prefill_batch,
            max_queue=self.max_queue,
        )
        self.prefill_batch = int(prefill_batch)
        # --- chunked prefill: pure scheduling — split the
        # non-shared prefill tail into prefill_chunk-token chunks that
        # ride ticks alongside the decode slab
        self.prefill_chunk: Optional[int] = None
        self.max_chunk_rows: Optional[int] = None
        self._chunk_policy: Optional[ChunkBudgetPolicy] = None
        if prefill_chunk:
            self._set_chunking(int(prefill_chunk), max_chunk_rows)
        elif max_chunk_rows is not None:
            raise ValueError("max_chunk_rows requires prefill_chunk")
        # --- speculative decoding: a prefix-slice draft
        # proposes spec_k tokens per tick, the target verifies all
        # spec_k+1 positions in one batched forward
        self.spec_k = int(spec_k)
        if self.spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        self.draft_blocks = (
            int(draft_blocks) if draft_blocks is not None else None
        )
        if self.spec_k > 0 and self.draft_blocks is None:
            raise ValueError(
                "spec_k > 0 requires draft_blocks (the prefix-"
                "slice depth of the draft model)"
            )
        self._draft: Optional[DraftModel] = None
        self.stats = ServingStats()
        # same snapshot() contract as the training runner's registry, so
        # one poller reads either subsystem identically
        self.metrics = MetricsRegistry()
        self.metrics.register("serving", lambda: self.stats.snapshot(),
                              types=ServingStats.FIELD_TYPES)
        # trace attribution name for request-scoped spans; a fleet
        # replica overwrites this with its replica name so a migrated
        # request's waterfall says WHERE each segment ran
        self.trace_name = "engine"
        # the span sinks of the current step() and the ring lane of its
        # engine-level spans: looked up once at the top of every step,
        # used by every phase under it
        self._sp = span_sinks()
        self._eng_lane = None
        # live observability (LiveMetricsMixin: enable_timeseries /
        # start_exporter — opt-in, zero-cost until enabled; step()
        # samples the series when one is attached)
        self.timeseries = None
        self._exporter = None
        self._running: Dict[int, Request] = {}  # request_id -> Request
        # chunked-prefill ledger: requests holding a page grant and a
        # decode row whose prefilled_len watermark has not reached the
        # end of their effective prompt (insertion order = enrollment
        # FIFO, which chunk waves honor head-first)
        self._prefilling: Dict[int, Request] = {}
        self._finished: List[Request] = []
        # closed-loop tuning: when set (tuning.ServingAutotuner attaches
        # itself here), every step ends with an observe/decide callback —
        # the serving twin of the Runner's AutotuneHook
        self.autotuner = None

        self._devices = (
            list(devices) if devices is not None else jax.devices()
        )
        # retained for reconfigure's re-run of the serving pre-flight
        # (slab memory vs budgets) against a proposed operating point;
        # the preflight opt-out carries over so both checks agree
        self._model_cfg = list(model_cfg)
        self._worker_manager = worker_manager
        self._preflight = bool(preflight)
        counts, stage_devices = self._resolve_stage_plan(
            worker_manager, partition, len(modules)
        )
        # the draft's only RESIDENT cost: a copy of the LM-head params
        # on stage 0's device when the head lives on another stage —
        # computed BEFORE the pre-flight so the verifier charges it
        self._draft_mb = (
            tree_param_mb(list(params_list)[-1])
            if self.spec_k > 0 and len(counts) > 1 else 0.0
        )
        if preflight and worker_manager is not None:
            # slabs allocate eagerly below, so an over-budget serving
            # plan must die HERE — before any slab materializes or any
            # stage program compiles — with the serving context named
            from ..analysis.plan_check import verify_plan

            verify_plan(
                list(model_cfg), worker_manager,
                (np.zeros((self.num_slots, 1), np.int32),),
                memory="error", check_donation=False,
                serving=self._serving_context(),
            ).raise_if_failed()
        if len(params_list) != len(modules):
            raise ValueError(
                f"got {len(params_list)} param trees for "
                f"{len(modules)} layers"
            )
        # host state: ONE page pool governs the page-id space across
        # all stages (page p = row p of every stage's slabs); rows are
        # the decode concurrency lanes, shared as every stage's `.pool`
        # facade, which fleet slot accounting and chaos leaks read
        self._pool = PagedKVCachePool(
            self.num_pages, self.page_size,
            self.max_pages_per_request,
            enable_prefix_cache=self.enable_prefix_cache,
            max_prefix_entries=self._max_prefix_entries,
            kv_dtype=self._pool_kv_dtype(),
        )
        self._rows = RowAllocator(self.max_concurrency)
        # request_id -> host page copies + resume state (swap pool)
        self._swapped: Dict[int, Dict[str, Any]] = {}
        # banked totals of pools replaced by reconfigure (counter
        # monotonicity across geometry changes)
        self._pool_base = dict(
            prefix_hits=0, prefix_tokens_reused=0, cow_copies=0,
            prefix_evictions=0,
        )
        self.stages: List[Any] = []
        cursor = 0
        for k, (n, dev) in enumerate(zip(counts, stage_devices)):
            # everything the traced programs depend on: the exact layer
            # configs of this stage's slice, the layout, the cache
            # depth, and the donation mode (the input SHAPES — bucket,
            # row count, page geometry — are jit cache keys already,
            # not closure identity)
            program_key = json.dumps(
                [self._model_cfg[cursor:cursor + n], kv_layout,
                 self.max_len, bool(_donation_enabled()),
                 self.kv_dtype, self.attn_impl],
                sort_keys=True, default=str,
            )
            stage = _ServingStage(
                k,
                modules[cursor:cursor + n],
                list(params_list)[cursor:cursor + n],
                dev,
                self.num_pages,
                self.page_size,
                program_key=program_key,
                kv_dtype=self.kv_dtype,
                attn_impl=self.attn_impl,
            )
            stage.pool = self._rows  # shared row ledger facade
            self.stages.append(stage)
            cursor += n
        self._last_device = self.stages[-1].device
        if self.spec_k > 0:
            self._draft = self._build_draft()
            # one source of truth for the resident charge (the
            # pre-stage estimate above used the same head params)
            self._draft_mb = self._draft.extra_param_mb

    def _pool_kv_dtype(self) -> str:
        """The page pool's storage dtype string: the quantization knob
        when set, else the model dtype — what the allocator accounts
        and the verifier charges (one formula, paging.paged_pool_mb)."""
        if self.kv_dtype is not None:
            return self.kv_dtype
        return str(_gcfg(self._model_cfg[0]["config"]).dtype)

    def _serving_context(self) -> Dict[str, Any]:
        """The operating point the pre-flight verifier charges."""
        ctx = dict(
            num_pages=self.num_pages, page_size=self.page_size,
            max_pages_per_request=self.max_pages_per_request,
            bucket=self.bucketer.max_bucket,
        )
        if self.kv_dtype is not None:
            # the quantized byte width (+ scale slabs) is what the
            # slabs will actually allocate — the verifier must
            # charge the same formula or the two could disagree
            ctx["kv_dtype"] = self.kv_dtype
        if self._draft_mb:
            # the speculative draft's head copy is real stage-0
            # residency — the verifier must see it
            ctx["draft_mb"] = self._draft_mb
        return ctx

    def _set_chunking(self, prefill_chunk: int,
                      max_chunk_rows: Optional[int]) -> None:
        """Validate + install the chunked-prefill operating point.
        ``prefill_chunk`` must be one of the prefill buckets so chunk
        waves reuse the per-bucket prefill programs (the recompile pin
        holds with zero new shapes)."""
        if prefill_chunk not in self.bucketer.buckets:
            raise ValueError(
                f"prefill_chunk={prefill_chunk} must be one of the "
                f"prefill buckets {list(self.bucketer.buckets)} — chunk "
                f"waves reuse the bucket programs"
            )
        rows = (
            int(max_chunk_rows) if max_chunk_rows is not None
            else self.prefill_batch
        )
        policy = ChunkBudgetPolicy(
            prefill_chunk, max_chunk_rows=rows,
            idle_chunk_rows=max(rows, self.prefill_batch * 2),
        )
        self.prefill_chunk = int(prefill_chunk)
        self.max_chunk_rows = rows
        self._chunk_policy = policy

    def _build_draft(self) -> DraftModel:
        """Construct the prefix-slice draft on stage 0 (fallible —
        called before any state mutates, both at construction and when
        ``reconfigure`` enables speculation)."""
        full_modules = [m for st in self.stages for m in st.modules]
        idx = draft_slice_indices(full_modules, self.draft_blocks)
        cut = idx[-2] + 1  # prefix length (idx = range(cut) + [head])
        stage0, last = self.stages[0], self.stages[-1]
        if cut > len(stage0.modules):
            raise ValueError(
                f"draft_blocks={self.draft_blocks} needs the first "
                f"{cut} layers resident on stage 0, which holds only "
                f"{len(stage0.modules)} — shrink the draft or deepen "
                f"stage 0 (the draft shares stage 0's params and slabs)"
            )
        head_module = full_modules[-1]
        if len(self.stages) > 1:
            head_params = jax.device_put(last.params[-1], stage0.device)
            extra_mb = tree_param_mb(head_params)
        else:
            head_params = stage0.params[-1]
            extra_mb = 0.0
        key = DraftModel.program_key(
            [self._model_cfg[i] for i in idx], self.max_len,
            attn_impl=self.attn_impl, kv_dtype=self.kv_dtype,
        )
        return DraftModel(
            list(stage0.modules[:cut]) + [head_module],
            list(stage0.params[:cut]) + [head_params],
            stage0.device,
            extra_param_mb=extra_mb,
            program_key=key,
            attn_impl=self.attn_impl,
        )

    def _pending_draft_mb(self) -> float:
        """The draft memory a spec-enable would ADD to stage 0 (the
        LM-head copy; 0 when it already lives there) — computed without
        allocating anything, so the pre-flight can charge it BEFORE
        :meth:`_build_draft` performs the device_put."""
        if len(self.stages) <= 1:
            return 0.0
        return tree_param_mb(self.stages[-1].params[-1])

    # --- construction helpers ----------------------------------------------
    def _resolve_stage_plan(self, worker_manager, partition, n_layers):
        """(layer counts, devices) per stage, from an allocator-written
        worker pool, an explicit partition, or the 1-stage default."""
        if worker_manager is not None and partition is not None:
            raise ValueError("pass worker_manager OR partition, not both")
        if worker_manager is not None:
            # the verifier's stage ordering (plan_check._stage_workers):
            # rank-sorted non-empty workers — one definition, so the
            # engine and the pre-flight can never disagree on stages
            from ..analysis.plan_check import _stage_workers

            workers = _stage_workers(worker_manager)
            counts = [len(w.model_config) for w in workers]
            stage_devices = [
                self._devices[w.device_index % len(self._devices)]
                for w in workers
            ]
        else:
            counts = (
                [int(c) for c in partition]
                if partition is not None else [n_layers]
            )
            stage_devices = [
                self._devices[k % len(self._devices)]
                for k in range(len(counts))
            ]
        if sum(counts) != n_layers or any(c < 1 for c in counts):
            raise ValueError(
                f"partition {counts} does not cover {n_layers} layers"
            )
        return counts, stage_devices

    # --- row ledger (one, shared: every stage's .pool IS self._rows) -------
    @property
    def free_slots(self) -> int:
        return self.stages[0].pool.free_slots

    # --- request-scoped tracing ---------------------------------------------
    # One stable id (request_id) threads the whole waterfall: every
    # segment span lands on the request's recycled trace lane with a
    # {"request", "replica"} attribution, and the open-segment mark
    # lives on the Request object itself so whoever ends the segment —
    # this engine, another engine after a migration, or the fleet over
    # a dead replica — can close it.  All helpers are no-ops when
    # tracing is disabled (tracer is None).

    def _trace_queued(self, request: Request, tracer) -> None:
        """Open a ``queue_wait`` segment (mark + ``queued`` instant)."""
        if tracer is None:
            return
        request.trace_marks["queued"] = tracer.now()
        lane = tracer.request_lane(request.request_id)
        if lane is not None:
            tracer.instant(
                "queued", lane,
                {"request": request.request_id,
                 "replica": self.trace_name},
            )

    def _trace_close_queue(self, request: Request, tracer,
                           end_us: Optional[float] = None,
                           **extra) -> None:
        """Close the open ``queue_wait`` segment, if any."""
        if tracer is None:
            return
        mark = request.trace_marks.pop("queued", None)
        if mark is None:
            return
        lane = tracer.request_lane(request.request_id, lease=False)
        if lane is None:
            return
        end = tracer.now() if end_us is None else end_us
        args = {"request": request.request_id,
                "replica": self.trace_name}
        args.update(extra)
        tracer.complete("queue_wait", lane, mark, args,
                        dur_us=end - mark)

    def _trace_enroll(self, request: Request, grant, tracer) -> None:
        """Chunked enrollment: admission instant, queue segment closed,
        and the request-lane ``prefill`` segment OPENED (it spans
        enrollment -> final chunk, closed by ``_trace_close_prefill``)."""
        if tracer is None:
            return
        now_us = tracer.now()
        tracer.instant(
            "admit", tracer.lane("serving", "engine"),
            {"request": request.request_id, "slot": request.slot,
             "pages": len(grant.page_table),
             "shared": grant.shared_tokens, "chunked": True},
        )
        self._trace_close_queue(request, tracer, end_us=now_us)
        request.trace_marks["prefill"] = now_us

    def _trace_close_prefill(self, request: Request, tracer,
                             end_us: Optional[float] = None,
                             **extra) -> None:
        """Close the open chunked ``prefill`` segment, if any."""
        if tracer is None:
            return
        mark = request.trace_marks.pop("prefill", None)
        if mark is None:
            return
        lane = tracer.request_lane(request.request_id, lease=False)
        if lane is None:
            return
        end = tracer.now() if end_us is None else end_us
        args = {"request": request.request_id,
                "replica": self.trace_name}
        args.update(extra)
        tracer.complete("prefill", lane, mark, args,
                        dur_us=end - mark)

    def _trace_close_decode(self, request: Request, tracer,
                            **extra) -> None:
        """Close the open ``decode`` segment, if any."""
        if tracer is None:
            return
        mark = request.trace_marks.pop("decode", None)
        if mark is None:
            return
        lane = tracer.request_lane(request.request_id, lease=False)
        if lane is None:
            return
        args = {"request": request.request_id,
                "replica": self.trace_name,
                "tokens": len(request.tokens)}
        args.update(extra)
        tracer.complete("decode", lane, mark, args)

    # --- request lifecycle --------------------------------------------------
    def submit(self, request: Request, *, force: bool = False) -> Request:
        """Queue a request (admitted onto a row on a later ``step``).

        With ``max_queue`` set, a full queue applies ``queue_policy``:
        ``"reject"`` refuses the newcomer (:class:`QueueFullError`
        propagates), ``"shed"`` displaces the oldest token-less queued
        request(s) — under overload the head has waited longest and is
        the most likely to have already blown its deadline — marking
        them ``REJECTED``.  Requests with committed tokens or a
        preemption history are never shed (their stream, or the
        admission promise already made for them, would be lost); when
        nothing is sheddable, ``"shed"`` degrades to reject.  Either
        way ``stats.queue_rejections`` counts every turned-away
        request: shedding is only acceptable when visible.

        ``force=True`` bypasses the bound and the policy — for
        re-queues of ALREADY-ADMITTED requests only (the fleet's
        migration path; preempt/reconfigure force internally): an
        admission promise, once made, survives a replica failure.
        """
        length = int(request.effective_prompt.size)
        if length + request.remaining > self.max_len:
            raise ValueError(
                f"prompt ({length}) + new tokens ({request.remaining}) "
                f"exceed max_len={self.max_len}"
            )
        tracer = get_tracer()
        try:
            # raises QueueFullError on a full bounded queue (unless
            # forced) and ValueError if no bucket fits
            self._queue.submit(request, force=force)
        except QueueFullError:
            if self.queue_policy == "shed":
                # shed until the newcomer fits: force re-queues
                # (preemption/reconfigure/migration) may have pushed the
                # queue past the bound, so one victim is not always
                # enough; requests with committed tokens are never
                # victims (shed_oldest), and when nothing is sheddable
                # the policy degrades to reject — losing generated
                # tokens is worse than turning a newcomer away
                while self._queue.depth >= (self.max_queue or 0):
                    shed = self._queue.shed_oldest()
                    if shed is None:
                        break
                    shed.status = REJECTED
                    self.stats.queue_rejections += 1
                    if tracer is not None:
                        tracer.instant(
                            "queue_shed",
                            tracer.lane("serving", "engine"),
                            {"shed": shed.request_id,
                             "admitted": request.request_id},
                        )
                        self._trace_close_queue(shed, tracer,
                                                shed=True)
                        lane = tracer.request_lane(
                            shed.request_id, lease=False)
                        if lane is not None:
                            tracer.instant(
                                "shed", lane,
                                {"request": shed.request_id,
                                 "replica": self.trace_name},
                            )
                        tracer.release_request_lane(shed.request_id)
                if self._queue.depth < (self.max_queue or 0):
                    self._queue.submit(request)
                    self.stats.admitted += 1
                    self.stats.queue_depth = self._queue.depth
                    self._trace_queued(request, tracer)
                    return request
            self.stats.queue_rejections += 1
            if tracer is not None:
                tracer.instant(
                    "queue_reject", tracer.lane("serving", "engine"),
                    {"request": request.request_id,
                     "depth": self._queue.depth},
                )
            raise
        self.stats.admitted += 1
        self.stats.queue_depth = self._queue.depth
        self._trace_queued(request, tracer)
        return request

    def preempt(self, request_id: int,
                mode: Optional[str] = None) -> Request:
        """Evict a running request; it re-queues and resumes with its
        token stream intact.

        ``mode`` (or the engine's ``preempt_policy``) picks between
        **recompute** (the KV prefix is rebuilt on re-admission) and
        **swap** — page contents copied to a host pool and paged back
        in on re-admission, no prefill replay.  ``"auto"`` chooses by
        resume cost (``paging.choose_preempt_mode``): recompute replays
        ``len(effective_prompt)`` tokens of prefill, swap moves the
        request's pages over the host link twice; a resume prefix that
        has outgrown every bucket forces swap — the case recomputation
        structurally cannot serve.
        """
        request = self._running.get(request_id)
        prefilling = False
        if request is None:
            request = self._prefilling.get(request_id)
            prefilling = request is not None
        if request is None:
            raise KeyError(f"request {request_id} is not running")
        if mode not in (None, "auto", "recompute", "swap"):
            # validate BEFORE any state is touched: an unknown mode
            # falling through the branches below would tear the request
            # down and then fail to re-queue it
            raise ValueError(
                f"preempt mode must be 'auto', 'recompute' or 'swap', "
                f"got {mode!r}"
            )
        if prefilling and mode == "swap":
            # a partial prefill's pages hold an incomplete prompt; a
            # swap record would resume mid-watermark on an engine that
            # may no longer chunk — recomputation replays it exactly
            raise ValueError(
                "a mid-prefill request preempts by recomputation only"
            )
        resume_len = int(request.effective_prompt.size)
        if prefilling:
            # validate the resume prefix still fits a bucket (the
            # re-queue requires one) BEFORE touching any state (a
            # failed preempt must leave the request where it was), then
            # recompute — no tokens were generated yet, so the replay
            # is the same admission the request already passed
            self.bucketer.bucket_for(resume_len)
            mode = "recompute"
        else:
            try:
                self.bucketer.bucket_for(resume_len)
                fits = True
            except ValueError:
                fits = False
            if mode is None:
                mode = self.preempt_policy
            if mode == "auto":
                mode = choose_preempt_mode(
                    resume_len, len(self._pool.table(request_id)),
                    self.page_size, recompute_feasible=fits,
                )
            if mode == "recompute" and not fits:
                # a request grown past the largest bucket cannot resume
                # by recomputation: raise the bucketer's own diagnostic
                # before any state is touched
                self.bucketer.bucket_for(resume_len)
        swap_record = None
        if mode == "swap":
            # host copies BEFORE any state mutates: a sentinel-padded
            # table keeps the gathered shape fixed, and np.asarray
            # forces the device work before the pages are freed
            table = np.full(
                (self.max_pages_per_request,), self.num_pages, np.int32
            )
            held = self._pool.table(request_id)
            table[: len(held)] = held
            data = [st.swap_out(table) for st in self.stages]
            swap_record = dict(
                pages=len(held), index=request.index, data=data,
                # integrity stamp, verified at swap-in: a record
                # corrupted while parked on the host must fall back to
                # recompute, never restore poisoned KV
                checksum=_swap_record_checksum(
                    len(held), request.index, data
                ),
            )
        if prefilling:
            self._prefilling.pop(request_id)
            request.prefilled_len = 0  # recompute replays the tail
        else:
            self._running.pop(request_id)
        self._rows.release(request.slot)
        self._pool.release(request_id)
        request.slot = None
        request.preemptions += 1
        self.stats.preemptions += 1
        if swap_record is not None:
            self._swapped[request_id] = swap_record
            self.stats.swap_outs += 1
        tracer = get_tracer()
        if tracer is not None:
            tracer.instant(
                "preempt", tracer.lane("serving", "engine"),
                {"request": request_id, "mode": mode},
            )
            # the request's decode segment ends here (the engine-lane
            # preempt instant above already carries the request id, so
            # the timeline keeps its marker without a duplicate that
            # would double trace-derived preemption counts); a
            # mid-prefill victim closes its chunked prefill segment
            self._trace_close_decode(request, tracer, preempted=True)
            self._trace_close_prefill(request, tracer, preempted=True)
        # force: the queue bound gates NEW admissions only — a preempted
        # request is already admitted and dropping it loses its tokens.
        # A swapped request needs no prefill bucket (its KV returns from
        # the host pool verbatim), so the bucket check is skipped — that
        # is exactly what lets swap serve resume prefixes recomputation
        # cannot.
        self._queue.submit(request, force=True,
                           require_bucket=(mode != "swap"))
        self.stats.queue_depth = self._queue.depth
        self._trace_queued(request, tracer)
        return request

    def drain(self) -> List[Request]:
        """Evict everything and return it, token streams intact: every
        running request is preempted (recomputation-style) and the queue
        emptied, FIFO order.  The fleet's migration primitive — the
        returned requests re-submit on another engine and resume by
        recomputing their KV prefix, so streams continue exactly.

        A running request whose resume prefix has outgrown the largest
        bucket cannot resume by recomputation; it STAYS RUNNING here
        (``preempt``'s validate-before-evict contract) and is not
        returned — the caller decides whether to keep stepping this
        engine until it finishes or declare it failed.

        Swap records are host-local (another engine has no access to
        this one's host pool), so migration resumes by re-prefilling
        the effective prompt — and any swap records held for queued
        requests are dropped with the same consequence."""
        for request_id in list(self._running) + list(self._prefilling):
            try:
                # cross-engine resume is recompute by construction
                self.preempt(request_id, mode="recompute")
            except ValueError:
                continue  # documented: not resumable, stays running
        drained = self._queue.drain()
        for r in drained:
            self._swapped.pop(r.request_id, None)
        tracer = get_tracer()
        if tracer is not None:
            # each drained request's queue_wait segment ends HERE (on
            # this engine); re-submission elsewhere opens a fresh one —
            # the migration gap stays visible, never an orphaned mark
            for r in drained:
                self._trace_close_queue(r, tracer, drained=True)
        self.stats.queue_depth = 0
        return drained

    def corrupt_swap_record(self, request_id: Optional[int] = None,
                            *, force: bool = False) -> Optional[int]:
        """Flip bits in a held swap record's host payload (the
        sanctioned ``swap_corruption`` chaos hook — host-pool rot,
        a DMA gone wrong — applied through the record surface, never
        by monkeypatching).

        Targets ``request_id``'s record when given, else the oldest
        held record.  With ``force`` and nothing parked, the oldest
        running request is swapped out first through the public
        ``preempt`` path (so there is always a record to poison).
        Returns the corrupted record's request id, or None when no
        record exists and none can be forced — the injector logs that
        honestly instead of inventing a fault that never happened."""
        if request_id is not None:
            if request_id not in self._swapped:
                raise KeyError(
                    f"request {request_id} holds no swap record"
                )
            rid = request_id
        elif self._swapped:
            rid = min(self._swapped)
        else:
            rid = None
            if force:
                # oldest running request first: smallest id = the
                # record most likely to be swapped back in soon
                for cand in sorted(self._running):
                    try:
                        self.preempt(cand, mode="swap")
                    except (ValueError, KeyError):
                        continue
                    rid = cand
                    break
            if rid is None:
                return None
        record = self._swapped[rid]
        pairs = record["data"][0]
        k_host, v_host = pairs[0]
        leaf = k_host.values if isinstance(k_host, QuantizedPages) \
            else k_host
        raw = bytearray(np.ascontiguousarray(leaf).tobytes())
        raw[0] ^= 0xFF
        bad = np.frombuffer(bytes(raw), dtype=leaf.dtype).reshape(
            leaf.shape
        )
        if isinstance(k_host, QuantizedPages):
            k_host = QuantizedPages(bad, k_host.scale)
        else:
            k_host = bad
        pairs[0] = (k_host, v_host)
        return rid

    # --- the disaggregated prefill/decode handoff plane ---------------------
    def export_handoff(self, request_id: int) -> tuple:
        """Detach a decoding request as a portable handoff: the request
        (token stream intact) plus its swap record (host page copies +
        checksum), ready for another engine's :meth:`import_handoff`.

        Rides the public preempt path in ``swap`` mode verbatim — same
        host copies, same checksum stamp, same fixed gather shape — so
        a handoff export counts as a preemption + swap-out in the
        stats, and the record popped here is byte-identical to what a
        local swap-in would have restored.  Only a request PAST prefill
        can export (its first token is seeded and its KV watermark is
        page-complete); mid-prefill requests raise, exactly as
        ``preempt(mode="swap")`` does.  The caller (the disagg pool
        front door) owns delivering the pair and conserving it in a
        ledger — after this returns, this engine holds NO state for the
        request."""
        request = self._running.get(request_id)
        if request is None:
            raise KeyError(
                f"request {request_id} is not decoding here"
            )
        if not request.tokens:
            raise ValueError(
                "a request hands off only after prefill seeded its "
                "first token"
            )
        if request.done:
            raise ValueError(
                "a finished request has nothing left to hand off"
            )
        self.preempt(request_id, mode="swap")
        record = self._swapped.pop(request_id)
        self._queue.remove(request)
        self.stats.queue_depth = self._queue.depth
        self.stats.handoffs_out += 1
        self.stats.handoff_bytes += _swap_record_nbytes(record["data"])
        tracer = get_tracer()
        if tracer is not None:
            tracer.instant(
                "handoff_out", tracer.lane("serving", "engine"),
                {"request": request_id, "pages": record["pages"]},
            )
            # the queue segment preempt just opened ends here: the
            # request leaves this engine entirely (the importing side
            # opens its own)
            self._trace_close_queue(request, tracer, drained=True)
        return request, record

    def import_handoff(self, request: Request, record: dict) -> bool:
        """Seat an exported handoff for swap-in resume — checksum
        verified FIRST, before the record touches any engine state.

        True: the record passed its integrity gate and is parked; the
        admission loop's existing swap-in path (``_admit`` →
        ``_swap_in``) restores the pages with NO prefill and decoding
        continues at the record's index — the resume path IS the
        swap-in path, no new compile shapes.  False: the checksum did
        not match (or the payload shape cannot fit this engine), the
        poisoned record is refused, ``handoff_failures`` counts it, and
        the request re-queues to recompute from its prompt — committed
        tokens intact, so the stream is exact either way.  A corrupt
        record whose resume prefix fits no bucket is FAILED with a
        reasoned verdict, mirroring ``_swap_in``'s corruption verdict.
        """
        rid = request.request_id
        if (rid in self._running or rid in self._prefilling
                or rid in self._swapped
                or any(r is request for r in self._queue.requests)):
            raise ValueError(
                f"request {rid} is already live on this engine"
            )
        pages = record.get("pages")
        index = record.get("index")
        data = record.get("data")
        ok = (
            isinstance(pages, int) and 1 <= pages
            and pages <= self.max_pages_per_request
            and isinstance(index, int) and index >= 1
            and isinstance(data, list) and len(data) == len(self.stages)
        )
        if ok:
            expect = record.get("checksum")
            ok = (expect is not None
                  and _swap_record_checksum(pages, index, data)
                  == expect)
        tracer = get_tracer()
        if ok:
            self._swapped[rid] = record
            # bytes were counted once at export — the exporting side
            # owns the payload accounting, so a fleet-level sum over
            # both pools counts each handoff's bytes exactly once
            self.stats.handoffs_in += 1
        else:
            self.stats.handoff_failures += 1
            if tracer is not None:
                tracer.instant(
                    "handoff_corrupt", tracer.lane("serving", "engine"),
                    {"request": rid},
                )
            try:
                self.bucketer.bucket_for(
                    int(request.effective_prompt.size)
                )
            except ValueError:
                request.status = FAILED
                request.fail_reason = (
                    "handoff record corrupted and the resume prefix "
                    "fits no bucket"
                )
                return False
        # force: the handoff was admitted on the exporting pool — the
        # promise survives the pool boundary; a verified record resumes
        # bucket-free (swap-in), a refused one re-buckets to recompute
        self._queue.submit(request, force=True, require_bucket=not ok)
        self.stats.queue_depth = self._queue.depth
        if tracer is not None:
            tracer.instant(
                "handoff_in", tracer.lane("serving", "engine"),
                {"request": rid, "verified": ok},
            )
        self._trace_queued(request, tracer)
        return ok

    @property
    def running_requests(self) -> List[Request]:
        """Requests currently holding a slot/row (read-only view).
        Includes chunked-prefill requests mid-watermark: they hold a
        decode row and a page grant, so fleet slot-accounting and
        migration must see them as live."""
        return list(self._prefilling.values()) + list(
            self._running.values()
        )

    @property
    def queued_requests(self) -> List[Request]:
        """Requests waiting for admission, FIFO order (read-only view)."""
        return list(self._queue.requests)

    def _finish(self, request: Request, now: float) -> None:
        self._rows.release(request.slot)
        # pages the radix index still references survive the release —
        # the prefix cache's retention, not a leak
        self._pool.release(request.request_id)
        request.slot = None
        request.status = FINISHED
        request.finished_s = now
        self._running.pop(request.request_id, None)
        self._finished.append(request)
        self.stats.finished += 1
        ttft = request.ttft_s()
        tpot = request.tpot_s()
        if ttft is not None:
            self.stats.ttft_s.append(ttft)
        if tpot is not None:
            self.stats.tpot_s.append(tpot)
        tracer = get_tracer()
        if tracer is not None:
            # terminal: close the decode segment, stamp the finish, and
            # recycle the request's lane for the next live request
            self._trace_close_decode(request, tracer)
            lane = tracer.request_lane(request.request_id,
                                       lease=False)
            if lane is not None:
                tracer.instant(
                    "finish", lane,
                    {"request": request.request_id,
                     "replica": self.trace_name,
                     "tokens": len(request.tokens)},
                )
            tracer.release_request_lane(request.request_id)

    # --- the continuous-batching loop ---------------------------------------
    def has_work(self) -> bool:
        return (bool(self._running) or bool(self._prefilling)
                or self._queue.depth > 0)

    def step(self) -> None:
        """One engine iteration: admit prefill waves (or, with
        ``prefill_chunk`` set, enroll admissions and advance at most a
        budgeted number of prefill chunks), then one decode tick over
        every row.  Requests join and leave the running batch only
        here, between decode steps — iteration-level scheduling; the
        chunk budget bounds how much prefill any single decode tick
        can wait behind."""
        sp = self._sp = span_sinks()
        eng = self._eng_lane = sp.lane("serving", "engine")
        with sp.span("sky.serve.step", eng,
                     {"iter": self.stats.iterations}):
            with sp.span("sky.serve.admit", eng):
                if self._queue.depth > 0 and self.free_slots == 0:
                    self.stats.queue_stalls += 1
                    if sp.tracer is not None:
                        sp.tracer.instant(
                            "queue_stall", eng,
                            {"queued": self._queue.depth},
                        )
                self._admit()
            if self._chunk_policy is not None:
                self._chunk_tick()
            if self.spec_k > 0 and self._draft is not None:
                self._spec_tick()
            else:
                self._decode_tick()
            with sp.span("sky.serve.sync", eng):
                self.stats.iterations += 1
                self.stats.queue_depth = self._queue.depth
                self.stats.batch_occupancy = self.stages[0].pool.occupancy
                self._sync_paged_stats()
                if self.timeseries is not None:
                    self.timeseries.sample()
                if self.autotuner is not None:
                    self.autotuner.on_step(self)

    def _sync_paged_stats(self) -> None:
        """Mirror the page pool's counters/gauges into ``ServingStats``
        (one owner for the numbers — the pool — one surface for the
        exporter).  ``_pool_base`` banks a replaced pool's totals so a
        geometry reconfigure never makes an engine-lifetime counter go
        backwards (the discipline ``FIELD_TYPES`` promises)."""
        pool, base = self._pool, self._pool_base
        self.stats.prefix_hits = base["prefix_hits"] + pool.prefix_hits
        self.stats.prefix_tokens_reused = (
            base["prefix_tokens_reused"] + pool.prefix_tokens_reused
        )
        self.stats.cow_copies = base["cow_copies"] + pool.cow_copies
        self.stats.prefix_evictions = (
            base["prefix_evictions"] + pool.prefix_evictions
        )
        self.stats.pages_in_use = pool.pages_in_use
        self.stats.free_pages = pool.free_pages

    def reconfigure(
        self,
        *,
        buckets: Optional[Sequence[int]] = None,
        num_slots: Optional[int] = None,
        prefill_batch: Optional[int] = None,
        num_pages: Optional[int] = None,
        page_size: Optional[int] = None,
        max_pages_per_request: Optional[int] = None,
        max_concurrency: Optional[int] = None,
        prefill_chunk: Optional[int] = None,
        max_chunk_rows: Optional[int] = None,
        spec_k: Optional[int] = None,
    ) -> None:
        """Apply a new serving operating point IN PLACE, between steps.

        The act half of the serving tuning loop: bucket set, row count,
        page geometry and prefill wave width are all shape knobs, so
        changing them means new compiled programs — but not a new
        engine.  The queue re-buckets under the new set; bucket and
        wave-width changes leave running requests decoding untouched.
        A concurrency change (``max_concurrency``; ``num_slots``
        aliases it, which is the name the autotuner proposes) re-seats
        the running batch recomputation-style on the SAME page pool
        (the :meth:`preempt` machinery: token streams preserved
        exactly, KV prefixes rebuilt on re-admission; swap records
        stay valid).  A page-geometry change (``num_pages`` /
        ``page_size`` / ``max_pages_per_request``) rebuilds pool +
        slabs — running requests resume by recomputation, the prefix
        cache restarts cold (its counters banked, never reset), and
        host swap records (whose page shapes died with the geometry)
        convert to recomputation resumes only after every affected
        request is proven to fit a prefill bucket.

        ``prefill_chunk`` and ``spec_k`` are the chunked-prefill and
        speculative-decoding knobs: ``None`` keeps the current setting,
        ``0`` disables.  Both are pure scheduling — no slab rebuild —
        but disabling chunking evicts mid-prefill requests back to the
        queue (recompute-style: no one would ever finish their chunks),
        a chunk size must be a member of the (new) bucket set, and a
        ``spec_k`` change retraces the verify program at its new
        ``Lq = spec_k + 1`` shape on the next tick (a visible one-time
        warmup, the same one construction pays per bucket).  Enabling
        speculation requires the engine to have been built with
        ``draft_blocks`` (the draft's layer slice is construction
        state).

        Verify-then-apply: the knob set passes the pre-flight verifier
        (``analysis/plan_check.verify_tuning_knobs``), a geometry
        change, a raised max bucket or a newly resident draft re-runs
        the constructor's serving memory pre-flight (budget-charged
        slabs, when the engine was built from a worker manager), a
        geometry change pre-builds the new slabs, and every live
        request is proven to fit the new operating point — all BEFORE
        any state is touched, so a rejected reconfigure
        (:class:`PlanError` / ``ValueError`` / a slab-allocation
        failure) leaves the engine exactly as it was.
        """
        from ..analysis.plan_check import verify_tuning_knobs

        if buckets is not None:
            # same normalization the constructor's ShapeBucketer applies,
            # so reconfigure accepts exactly the inputs construction
            # does; a malformed entry is left raw for the knob verifier
            # to reject with a diagnostic (never a bare TypeError here)
            try:
                new_buckets = tuple(sorted(set(int(b) for b in buckets)))
            except (TypeError, ValueError):
                new_buckets = tuple(buckets)
        else:
            new_buckets = self.bucketer.buckets
        if max_concurrency is not None and num_slots is not None and (
                int(max_concurrency) != int(num_slots)):
            raise ValueError(
                "num_slots aliases max_concurrency; "
                f"got conflicting {num_slots} and {max_concurrency}"
            )
        new_rows = int(
            max_concurrency if max_concurrency is not None
            else num_slots if num_slots is not None
            else self.max_concurrency
        )
        new_batch = (
            int(prefill_batch)
            if prefill_batch is not None else self.prefill_batch
        )
        new_pages = (
            int(num_pages) if num_pages is not None else self.num_pages
        )
        new_psize = (
            int(page_size) if page_size is not None else self.page_size
        )
        new_mpr = (
            int(max_pages_per_request)
            if max_pages_per_request is not None
            else self.max_pages_per_request
        )
        new_virtual = new_mpr * new_psize if (
            isinstance(new_mpr, int) and isinstance(new_psize, int)
            and new_mpr > 0 and new_psize > 0
        ) else self.max_len
        # chunk / speculation knobs: None keeps, 0 disables
        new_chunk = (
            self.prefill_chunk if prefill_chunk is None
            else (int(prefill_chunk) or None)
        )
        new_chunk_rows = (
            int(max_chunk_rows) if max_chunk_rows is not None
            else self.max_chunk_rows
        )
        if max_chunk_rows is not None and new_chunk is None:
            # mirror the constructor: a rows knob with chunking off
            # (or being disabled here) must fail loudly, not silently
            # drop the operator's starvation bound
            raise ValueError("max_chunk_rows requires prefill_chunk")
        new_spec = self.spec_k if spec_k is None else int(spec_k)
        verify_tuning_knobs(
            buckets=new_buckets, max_len=new_virtual,
            num_slots=new_rows, prefill_batch=new_batch,
            num_pages=new_pages, page_size=new_psize,
            max_pages_per_request=new_mpr,
            prefill_chunk=new_chunk, spec_k=new_spec,
        ).raise_if_failed()
        if new_spec > 0 and self._draft is None and (
                self.draft_blocks is None):
            raise ValueError(
                "reconfigure rejected: spec_k > 0 requires an engine "
                "built with draft_blocks (the draft's layer slice is "
                "construction state)"
            )
        max_pos = _gcfg(
            self.stages[0].modules[0].config
        ).max_position_embeddings
        if new_virtual > max_pos:
            raise ValueError(
                f"max_pages_per_request x page_size = {new_virtual} "
                f"exceeds max_position_embeddings={max_pos}"
            )
        geometry_change = (
            new_pages != self.num_pages or new_psize != self.page_size
            or new_mpr != self.max_pages_per_request
        )
        rows_change = new_rows != self.max_concurrency
        must_evict = geometry_change or rows_change
        # an enable of speculation makes the draft's LM-head copy newly
        # resident on stage 0 — that is real memory the verifier must
        # see BEFORE _build_draft's device_put allocates it
        enabling_spec = new_spec > 0 and self._draft is None
        charged_draft_mb = (
            self._pending_draft_mb() if enabling_spec else self._draft_mb
        )
        if (self._preflight and self._worker_manager is not None
                and (geometry_change
                     or max(new_buckets) > self.bucketer.max_bucket
                     or (enabling_spec and charged_draft_mb > 0))):
            # ANY geometry change pre-builds a full second slab set
            # while the old one is still resident, so the transient
            # peak is old+new pool depth even when the new pool is
            # SMALLER — charge exactly what the apply holds: that
            # transient peak, not the steady state, is what must fit
            from ..analysis.plan_check import verify_plan

            charged = new_pages + (
                self.num_pages if geometry_change else 0
            )
            ctx = dict(num_pages=charged, page_size=new_psize,
                       max_pages_per_request=new_mpr,
                       bucket=max(new_buckets))
            if self.kv_dtype is not None:
                ctx["kv_dtype"] = self.kv_dtype
            if charged_draft_mb > 0:
                ctx["draft_mb"] = charged_draft_mb
            verify_plan(
                self._model_cfg, self._worker_manager,
                (np.zeros((new_rows, 1), np.int32),),
                memory="error", check_donation=False,
                serving=ctx,
            ).raise_if_failed()
        # (an off-bucket prefill_chunk was already rejected by
        # verify_tuning_knobs above — the one enforcement point)
        new_bucketer = ShapeBucketer(new_buckets)
        # feasibility BEFORE any mutation.  Swap records survive only a
        # geometry-preserving change; under a geometry change every
        # swapped request must be able to resume by recomputation.
        live = (list(self._running.values())
                + list(self._prefilling.values())
                + list(self._queue.requests))
        for r in live:
            length = int(r.effective_prompt.size)
            swapped = r.request_id in self._swapped
            if length + r.remaining > new_virtual:
                raise ValueError(
                    f"reconfigure rejected: request {r.request_id} "
                    f"spans {length + r.remaining} positions; the new "
                    f"virtual span is {new_virtual}"
                )
            if swapped and not geometry_change:
                continue  # resumes from host pages, needs no bucket
            try:
                new_bucketer.bucket_for(length)
            except ValueError as exc:
                raise ValueError(
                    f"reconfigure rejected: request {r.request_id} "
                    f"cannot resume under buckets {list(new_buckets)}: "
                    f"{exc}"
                ) from None
        # pre-build everything fallible BEFORE touching request state
        new_slabs = (
            [st.build_slabs(new_pages, new_psize) for st in self.stages]
            if geometry_change else None
        )
        new_pool = (
            PagedKVCachePool(
                new_pages, new_psize, new_mpr,
                enable_prefix_cache=self.enable_prefix_cache,
                max_prefix_entries=self._max_prefix_entries,
                kv_dtype=self._pool_kv_dtype(),
            )
            if geometry_change else None
        )
        new_row_alloc = RowAllocator(new_rows) if must_evict else None
        # pre-build the fallible chunk/spec machinery before mutation
        new_policy = None
        if new_chunk is not None:
            rows = (
                new_chunk_rows if new_chunk_rows is not None
                else new_batch
            )
            new_policy = ChunkBudgetPolicy(
                new_chunk, max_chunk_rows=rows,
                idle_chunk_rows=max(rows, new_batch * 2),
            )
        new_draft = self._draft
        if new_spec > 0 and new_draft is None:
            new_draft = self._build_draft()

        tracer = get_tracer()
        old = dict(buckets=list(self.bucketer.buckets),
                   max_concurrency=self.max_concurrency,
                   prefill_batch=self.prefill_batch,
                   num_pages=self.num_pages, page_size=self.page_size,
                   max_pages_per_request=self.max_pages_per_request,
                   prefill_chunk=self.prefill_chunk,
                   spec_k=self.spec_k)
        evicted: List[Request] = []

        def evict(r: Request, prefilling: bool) -> None:
            if prefilling:
                self._prefilling.pop(r.request_id)
                r.prefilled_len = 0  # recompute replays the tail
            else:
                self._running.pop(r.request_id)
            self._rows.release(r.slot)
            self._pool.release(r.request_id)
            r.slot = None
            r.preemptions += 1
            self.stats.preemptions += 1
            evicted.append(r)
            if tracer is not None:
                tracer.instant(
                    "preempt", tracer.lane("serving", "engine"),
                    {"request": r.request_id, "reconfigure": True},
                )
                self._trace_close_decode(r, tracer, reconfigure=True)
                self._trace_close_prefill(r, tracer, reconfigure=True)

        if must_evict:
            for r in list(self._running.values()):
                evict(r, prefilling=False)
            for r in list(self._prefilling.values()):
                evict(r, prefilling=True)
        elif new_chunk is None and self._prefilling:
            # chunking turned off with requests mid-watermark: no chunk
            # tick would ever finish them — re-queue recompute-style
            for r in list(self._prefilling.values()):
                evict(r, prefilling=True)
        queued = self._queue.drain()
        if tracer is not None:
            for r in queued:
                self._trace_close_queue(r, tracer, rebucketed=True)
        if geometry_change:
            # bank the dying pool's counters (monotonic discipline),
            # then swap in the cold pool + fresh slabs; swap records'
            # page shapes died with the geometry -> recompute resumes
            self._pool_base["prefix_hits"] += self._pool.prefix_hits
            self._pool_base["prefix_tokens_reused"] += (
                self._pool.prefix_tokens_reused
            )
            self._pool_base["cow_copies"] += self._pool.cow_copies
            self._pool_base["prefix_evictions"] += (
                self._pool.prefix_evictions
            )
            self._pool = new_pool
            for st, slabs in zip(self.stages, new_slabs):
                st.num_pages = new_pages
                st.page_size = new_psize
                st.slabs = slabs
            self._swapped.clear()
            self.num_pages = new_pages
            self.page_size = new_psize
            self.max_pages_per_request = new_mpr
            self.max_len = new_virtual
        if new_row_alloc is not None:
            self._rows = new_row_alloc
            for st in self.stages:
                st.pool = self._rows
            self.max_concurrency = new_rows
            self.num_slots = new_rows
        self.bucketer = new_bucketer
        self.prefill_batch = new_batch
        self.prefill_chunk = new_chunk
        self.max_chunk_rows = (
            new_policy.max_chunk_rows if new_policy is not None else None
        )
        self._chunk_policy = new_policy
        self.spec_k = new_spec
        if new_spec > 0:
            self._draft = new_draft
            self._draft_mb = new_draft.extra_param_mb
        self._queue = AdmissionQueue(new_bucketer, prefill_batch=new_batch,
                                     max_queue=self.max_queue)
        for r in evicted + queued:
            self._queue.submit(
                r, force=True,
                require_bucket=not (
                    r.request_id in self._swapped
                ),
            )
            self._trace_queued(r, tracer)
        self.stats.queue_depth = self._queue.depth
        if tracer is not None:
            tracer.instant(
                "reconfigure", tracer.lane("serving", "engine"),
                dict(old=old,
                     new=dict(buckets=list(new_buckets),
                              max_concurrency=new_rows,
                              prefill_batch=new_batch,
                              num_pages=new_pages, page_size=new_psize,
                              max_pages_per_request=new_mpr,
                              prefill_chunk=new_chunk,
                              spec_k=new_spec),
                     evicted=len(evicted)),
            )

    def run(
        self,
        requests: Optional[Sequence[Request]] = None,
        max_iterations: int = 100_000,
    ) -> Dict[int, np.ndarray]:
        """Drive ``step`` until the queue and batch drain; returns
        ``{request_id: prompt + generated tokens}`` for everything that
        finished during the call."""
        finished0 = len(self._finished)
        for r in requests or ():
            self.submit(r)
        for _ in range(max_iterations):
            if not self.has_work():
                break
            self.step()
        else:  # pragma: no cover - scheduler liveness guard
            raise RuntimeError(
                f"serving engine made no full drain in "
                f"{max_iterations} iterations"
            )
        return {
            r.request_id: r.output()
            for r in self._finished[finished0:]
        }

    @property
    def finished_requests(self) -> List[Request]:
        return list(self._finished)

    # --- live observability (LiveMetricsMixin provides the wiring) ----------
    def _health_snapshot(self) -> Dict[str, Any]:
        return dict(
            status="ok",
            queue_depth=self._queue.depth,
            running=len(self._running),
            free_slots=self.free_slots,
            iterations=self.stats.iterations,
            kv_layout="paged",
            free_pages=self._pool.free_pages,
            pages_in_use=self._pool.pages_in_use,
            swapped=len(self._swapped),
            prefilling=len(self._prefilling),
            # the active kernel/quantization operating point, so a
            # scrape can tell WHICH decode path a replica runs
            kv_dtype=self._pool.kv_dtype,
            attn_impl=self.attn_impl,
        )

    # --- internals ----------------------------------------------------------
    def _admit(self) -> None:
        """Admit from the queue while rows AND pages allow — admission
        charges PAGES (the request's reserved footprint), so
        concurrency floats with actual memory use.  FIFO: the head
        either admits (prefill wave or swap-in) or stalls the queue — a
        later small request never jumps a starved head."""
        sp, eng = self._sp, self._eng_lane
        while True:
            queued = self._queue.requests
            if not queued or self._rows.free_slots < 1:
                return
            head = queued[0]
            if head.request_id in self._swapped:
                with sp.span("sky.serve.swap_in", eng,
                             {"request": head.request_id}):
                    swapped_in = self._swap_in(head)
                if not swapped_in:
                    if head.request_id in self._swapped:
                        # pages genuinely unavailable: the head stalls
                        # the queue until a release frees them
                        self._stall_on_pages()
                        return
                    # corrupt record dropped (or the victim FAILED):
                    # re-judge the head as a normal recompute admission
                    continue
                continue
            if self._chunk_policy is not None:
                # chunked admission is charge-only (no compute): the
                # head gets its page grant and decode row, then its
                # prefill rides budgeted chunk waves across later ticks
                if not self._enroll_chunked(head):
                    self._stall_on_pages()
                    return
                continue
            with sp.span("sky.serve.select_wave", eng):
                wave = self._select_wave()
            if wave is None:
                self._stall_on_pages()
                return
            self._prefill_wave(wave)

    def _enroll_chunked(self, request: Request) -> bool:
        """Admit the queue head under chunked prefill: charge its page
        grant, seat it on a decode row, perform the grant's COW copy,
        and set the ``prefilled_len`` watermark at the shared-prefix
        boundary.  No prefill compute happens here — chunk waves do
        that, budgeted per tick.  False (nothing mutated) when the
        pages cannot be charged yet."""
        tokens = self._effective_tokens(request)
        grant = self._pool.acquire(
            request.request_id, tokens, len(tokens) + request.remaining
        )
        if grant is None:
            return False
        row = self._rows.allocate()
        assert row is not None  # caller checked free rows
        request.slot = row
        # COW before any chunk write: the donor's partial page becomes
        # this request's private page (same rule as the one-shot wave);
        # the pool's plan decides what a clone copies (scale rows ride
        # along on an int8 pool)
        with self._sp.span("sky.serve.cow", self._eng_lane):
            plan = self._pool.cow_plan(grant)
            if plan:
                for st in self.stages:
                    st.apply_cow_plan(plan)
        self._queue.remove(request)
        request.prefilled_len = grant.shared_tokens
        request.status = RUNNING
        self._prefilling[request.request_id] = request
        self.stats.queue_depth = self._queue.depth
        tracer = get_tracer()
        self._trace_enroll(request, grant, tracer)
        return True

    def _chunk_tick(self) -> None:
        """Advance chunked prefill by at most the policy's budget:
        head-fixes-the-bucket chunk waves (enrollment FIFO) until the
        budget is spent, each request advancing AT MOST ONE chunk per
        tick (fairness: the head can never eat the whole budget while
        later enrollees starve).  A tick that leaves some mid-prefill
        request without a chunk counts one ``chunk_stalls`` — work was
        actually deferred, the deliberate price of protecting decode
        latency."""
        if not self._prefilling:
            return
        budget = self._chunk_policy.rows_for_tick(
            pending=len(self._prefilling), decoding=len(self._running)
        )
        advanced: set = set()
        while budget > 0:
            wave = self._select_chunk_wave(
                min(budget, self.prefill_batch), advanced
            )
            if not wave:
                break
            advanced.update(r.request_id for r in wave)
            self._chunk_wave(wave)
            budget -= len(wave)
        # requests still mid-watermark that got NO chunk this tick:
        # the budget (or a bucket mismatch past it) deferred real work
        deferred = [
            rid for rid in self._prefilling if rid not in advanced
        ]
        if deferred:
            self.stats.chunk_stalls += 1
            tracer = get_tracer()
            if tracer is not None:
                tracer.instant(
                    "chunk_stall", tracer.lane("serving", "engine"),
                    {"deferred": len(deferred)},
                )

    def _next_chunk_len(self, request: Request) -> int:
        return min(
            self.prefill_chunk,
            int(request.effective_prompt.size) - request.prefilled_len,
        )

    def _select_chunk_wave(self, cap: int,
                           exclude: set) -> List[Request]:
        """Up to ``cap`` mid-prefill requests whose NEXT chunk pads to
        the enrollment head's bucket (same-bucket packing, FIFO head
        never skipped — the wave-selection rule at chunk granularity).
        ``exclude`` holds requests already advanced this tick, so one
        tick never gives the head a second chunk while others wait."""
        pending = [
            r for r in self._prefilling.values()
            if r.request_id not in exclude
        ]
        if not pending:
            return []
        head = pending[0]
        bucket = self.bucketer.bucket_for(self._next_chunk_len(head))
        wave: List[Request] = []
        for r in pending:
            if len(wave) >= cap:
                break
            if self.bucketer.bucket_for(
                    self._next_chunk_len(r)) == bucket:
                wave.append(r)
        return wave

    def _chunk_wave(self, wave: List[Request]) -> None:
        """One prefill-chunk wave: each member's next
        ``<= prefill_chunk`` prompt positions, padded to the wave
        bucket, scattered through the members' page tables at their
        ``prefilled_len`` watermarks — the SAME compiled program shape
        as a tail-prefill wave, so chunking adds zero compiles.  A
        member whose watermark reaches its prompt end commits its
        first token and joins the decode batch."""
        rows = self.prefill_batch
        sp, eng = self._sp, self._eng_lane
        tracer = sp.tracer
        chunks = []
        for r in wave:
            eff = r.effective_prompt
            clen = self._next_chunk_len(r)
            chunks.append(eff[r.prefilled_len:r.prefilled_len + clen])
        bucket = self.bucketer.bucket_for(int(chunks[0].size))
        # per-chunk TRUE token counts: the padding-waste histogram and
        # serving_padding_fraction() must see what this wave actually
        # prefilled, never the members' full prompt lengths
        wave_tokens = int(sum(int(c.size) for c in chunks))
        wave_args = {"bucket": bucket, "wave": len(wave),
                     "tokens": wave_tokens, "chunk": True}
        with sp.span("sky.serve.prefill", eng, wave_args):
            with sp.span("sky.serve.build", eng):
                ids, lengths = self.bucketer.pad_batch(
                    chunks, bucket, rows, self.pad_id
                )
                sentinel = self.num_pages
                tables = np.full(
                    (rows, self.max_pages_per_request), sentinel, np.int32
                )
                index = np.zeros((rows,), np.int32)
                valid = np.zeros((rows,), np.int32)  # pad rows: writes drop
                for i, r in enumerate(wave):
                    held = self._pool.table(r.request_id)
                    tables[i, : len(held)] = held
                    index[i] = r.prefilled_len
                    valid[i] = r.prefilled_len + int(chunks[i].size)

                width = self._table_width(valid)
                tables = tables[:, :width]
                self._count_quant(index, valid, width, len(wave))
            t0 = time.perf_counter()
            compiles0 = xla_compile_count()
            run_args = wave_args if tracer is None else dict(
                wave_args, requests=[r.request_id for r in wave])
            with sp.span("sky.serve.run", eng, run_args,
                         ring="prefill") as run:
                data = self._run_stages(
                    ids, tables, index, valid, "prefill",
                    {"bucket": bucket, "chunk": True},
                )
                pos = device_put_elided(lengths - 1, self._last_device)
                logits = _gather_last(data, pos)  # [rows, V]
                tokens = _argmax_tokens(logits)
                with sp.span("sky.serve.wait", eng):
                    jax.block_until_ready(tokens)
            now = time.perf_counter()
            self.stats.prefill_s += now - t0
            with sp.span("sky.serve.commit", eng):
                self.stats.prefill_waves += 1
                self.stats.prefill_tokens += wave_tokens
                self.stats.prefill_chunks += len(wave)
                self.stats.compiles += xla_compile_count() - compiles0

                finals = [
                    (i, r) for i, r in enumerate(wave)
                    if r.prefilled_len + int(chunks[i].size)
                    >= int(r.effective_prompt.size)
                ]
                tokens_np = np.asarray(tokens)
                sampled = self._sampled_rows(logits, finals)
                for i, r in enumerate(wave):
                    clen = int(chunks[i].size)
                    r.prefilled_len += clen
                    if r.prefilled_len < int(r.effective_prompt.size):
                        continue  # watermark advanced; more chunks to come
                    # final chunk: the last true position's logits seed
                    # the first generated token, exactly like a one-shot
                    # wave
                    self._prefilling.pop(r.request_id)
                    self._pool.register_prefix(
                        r.request_id, [int(t) for t in r.prompt]
                    )
                    tok = self._pick_token(r, tokens_np[i], sampled.get(i))
                    r.tokens.append(tok)
                    r.index = r.prefilled_len
                    r.prefilled_len = 0
                    r.status = RUNNING
                    self._running[r.request_id] = r
                    if r.first_token_s is None:
                        r.first_token_s = now
                    self.stats.generated_tokens += 1
                    if tracer is not None:
                        self._trace_close_prefill(
                            r, tracer, end_us=run.end_us, bucket=bucket,
                            slot=r.slot)
                        r.trace_marks["decode"] = run.end_us
                    if r.done:
                        self._finish(r, now)

    @staticmethod
    def _effective_tokens(request: Request) -> tuple:
        """The request's effective prompt as a token tuple (radix-cache
        key), cached until its generated-token count changes — wave
        selection re-scans the queue every stalled tick, and rebuilding
        O(prompt) int lists per scan would put host work proportional
        to queue depth x prompt length on the scheduling path."""
        n = len(request.tokens)
        cached = getattr(request, "_token_cache", None)
        if cached is not None and cached[0] == n:
            return cached[1]
        tokens = tuple(int(t) for t in request.effective_prompt)
        request._token_cache = (n, tokens)
        return tokens

    def _stall_on_pages(self) -> None:
        """Count a page-exhaustion stall (the row-exhaustion twin is
        counted by ``step``; rows were free here, pages were not)."""
        self.stats.queue_stalls += 1
        tracer = get_tracer()
        if tracer is not None:
            tracer.instant(
                "queue_stall", tracer.lane("serving", "engine"),
                {"queued": self._queue.depth,
                 "free_pages": self._pool.free_pages},
            )

    def _select_wave(self) -> Optional[List[Any]]:
        """Dequeue the next prefill wave, or None when the head cannot
        be charged.

        The head's TAIL bucket (prompt minus its radix-shared prefix)
        fixes the wave's compile shape; later queued requests whose
        tails land in the same bucket pack in, each charged its own
        page grant.  Buckets are pure compile-shape classes here —
        admission capacity is pages + rows.
        """
        queued = self._queue.requests
        head = queued[0]
        cap = min(self.prefill_batch, self._rows.free_slots)
        wave: List[Any] = []
        bucket: Optional[int] = None
        for r in queued:
            if len(wave) >= cap:
                break
            if r.request_id in self._swapped:
                continue  # swap-ins ride their own admission path
            tokens = self._effective_tokens(r)
            length = len(tokens)
            if bucket is not None:
                # cheap pre-screen before charging pages
                peek = self._pool.peek_shared(tokens)
                if self.bucketer.bucket_for(
                        max(1, length - peek)) != bucket:
                    continue
            grant = self._pool.acquire(
                r.request_id, tokens, length + r.remaining
            )
            if grant is None:
                if r is head:
                    return None  # head starves -> the queue stalls
                break  # pages ran out mid-pack; serve what we have
            tail_bucket = self.bucketer.bucket_for(
                length - grant.shared_tokens
            )
            if bucket is None:
                bucket = tail_bucket
            elif tail_bucket != bucket:
                # the peek promised this bucket but the grant (made
                # under eviction) disagreed: hand the pages back with
                # the hit counters reversed and move on
                self._pool.rollback_grant(grant)
                continue
            wave.append((r, grant))
        if not wave:
            return None
        for r, _ in wave:
            self._queue.remove(r)
        return wave

    def _prefill_wave(self, wave: List[Any]) -> None:
        """Prefill a wave of (request, grant) pairs: COW-clone partial
        shared pages, compute ONLY the non-shared tails, scatter their
        K/V through the page tables, and seat each request on a decode
        row.  A full-prefix hit costs one bucket of tail compute — the
        TTFT-drops-with-prefix-length effect."""
        rows = self.prefill_batch
        sp, eng = self._sp, self._eng_lane
        tracer = sp.tracer
        tails = [
            r.effective_prompt[g.shared_tokens:] for r, g in wave
        ]
        bucket = self.bucketer.bucket_for(int(tails[0].size))
        wave_tokens = int(sum(int(t.size) for t in tails))
        shared_tokens = int(sum(g.shared_tokens for _, g in wave))
        wave_args = {"bucket": bucket, "wave": len(wave),
                     "tokens": wave_tokens, "shared": shared_tokens}
        with sp.span("sky.serve.prefill", eng, wave_args):
            with sp.span("sky.serve.build", eng):
                ids, lengths = self.bucketer.pad_batch(
                    tails, bucket, rows, self.pad_id
                )
                sentinel = self.num_pages
                tables = np.full(
                    (rows, self.max_pages_per_request), sentinel, np.int32
                )
                index = np.zeros((rows,), np.int32)
                # pad rows: every write drops
                valid = np.zeros((rows,), np.int32)
                for i, (r, g) in enumerate(wave):
                    row = self._rows.allocate()
                    assert row is not None  # wave capped by free rows
                    r.slot = row
                    tables[i, : len(g.page_table)] = g.page_table
                    index[i] = g.shared_tokens
                    valid[i] = g.shared_tokens + int(tails[i].size)
                width = self._table_width(valid)
                tables = tables[:, :width]
                self._count_quant(index, valid, width, len(wave))
            # copy-on-write BEFORE any dispatch touches the slabs: the
            # donor's partial page becomes the sharer's private page, so
            # the tail prefill's appends never write a shared page; the
            # pool's plan decides what a clone copies (scale rows ride
            # along on an int8 pool)
            with sp.span("sky.serve.cow", eng):
                for _, g in wave:
                    plan = self._pool.cow_plan(g)
                    if plan:
                        for st in self.stages:
                            st.apply_cow_plan(plan)

            t0 = time.perf_counter()
            compiles0 = xla_compile_count()
            run_args = wave_args if tracer is None else dict(
                wave_args, requests=[r.request_id for r, _ in wave])
            with sp.span("sky.serve.run", eng, run_args,
                         ring="prefill") as run:
                data = self._run_stages(
                    ids, tables, index, valid, "prefill",
                    {"bucket": bucket},
                )
                pos = device_put_elided(lengths - 1, self._last_device)
                logits = _gather_last(data, pos)  # [rows, V]
                tokens = _argmax_tokens(logits)
                with sp.span("sky.serve.wait", eng):
                    jax.block_until_ready(tokens)
            now = time.perf_counter()
            self.stats.prefill_s += now - t0
            with sp.span("sky.serve.commit", eng):
                if tracer is not None:
                    for r, g in wave:
                        tracer.instant(
                            "admit", eng,
                            {"request": r.request_id, "slot": r.slot,
                             "pages": len(g.page_table),
                             "shared": g.shared_tokens},
                        )
                        self._trace_close_queue(r, tracer,
                                                end_us=run.start_us)
                        lane = tracer.request_lane(r.request_id,
                                                   lease=False)
                        if lane is not None:
                            tracer.complete(
                                "prefill", lane, run.start_us,
                                {"request": r.request_id,
                                 "replica": self.trace_name,
                                 "bucket": bucket, "slot": r.slot,
                                 "shared": g.shared_tokens},
                                dur_us=run.end_us - run.start_us,
                            )
                        r.trace_marks["decode"] = run.end_us
                self.stats.prefill_waves += 1
                self.stats.prefill_tokens += wave_tokens
                self.stats.compiles += xla_compile_count() - compiles0

                tokens_np = np.asarray(tokens)
                sampled = self._sampled_rows(
                    logits, [(i, r) for i, (r, _) in enumerate(wave)]
                )
                for i, (r, g) in enumerate(wave):
                    # index the radix cache BEFORE the done-check can
                    # release the pages: a request that finishes in its
                    # prefill tick still leaves its prompt warm for the
                    # next sharer
                    self._pool.register_prefix(
                        r.request_id, [int(t) for t in r.prompt]
                    )
                    tok = self._pick_token(r, tokens_np[i], sampled.get(i))
                    r.tokens.append(tok)
                    r.index = int(valid[i])
                    r.status = RUNNING
                    self._running[r.request_id] = r
                    if r.first_token_s is None:
                        r.first_token_s = now
                    self.stats.generated_tokens += 1
                    if r.done:
                        self._finish(r, now)

    def _table_width(self, valid) -> int:
        """Page-table columns this step actually needs: the wave's max
        live length, ceiled to a page, then to the next power-of-two
        page count with the largest bucket's span as floor — so the XLA
        reference gathers O(live tokens), not O(max_pages) (the kernel
        walks each row's own live pages whatever the width;
        ``attn_pages_live``), while the distinct compile-shape set
        stays logarithmic and warmable exactly like prefill buckets."""
        need = max(1, pages_for(int(np.max(valid)), self.page_size))
        floor = pages_for(self.bucketer.max_bucket, self.page_size)
        width = max(need, floor)
        p = 1
        while p < width:
            p <<= 1
        return min(p, self.max_pages_per_request)

    def _count_quant(self, index, valid, width: int, rows: int) -> None:
        """int8 observability: bank this step's quantize/dequant work.
        ``quantized_pages`` = pages the write wave touched (each one
        re-quantized through its scale); ``dequant_blocks`` = page
        blocks attention dequantized (active rows x gathered width) —
        both per step, across all stages' layers would just scale by a
        constant, so the per-step count is the honest unit."""
        if self.kv_dtype != "int8":
            return
        index = np.asarray(index)
        valid = np.asarray(valid)
        live = valid > index
        if np.any(live):
            touched = (
                (valid[live] - 1) // self.page_size
                - index[live] // self.page_size + 1
            )
            self.stats.quantized_pages += int(touched.sum())
        self.stats.dequant_blocks += int(rows) * int(width)

    def _count_attn_pages(self, query_ends, width: int) -> Dict[str, int]:
        """Bank one decode forward's page walk and return it as
        span arguments.  ``query_ends``: ``index + Lq`` of each active
        row; the program's other rows sit at index 0 and cost the one
        page the kernel always reads."""
        ends = np.asarray(query_ends, np.int64)
        idle = self.max_concurrency - ends.size
        live = np.minimum(-(-ends // self.page_size), width)
        counts = {
            "attn_pages_live": int(live.sum()) + idle,
            "attn_pages_table": self.max_concurrency * int(width),
        }
        self.stats.attn_pages_live += counts["attn_pages_live"]
        self.stats.attn_pages_table += counts["attn_pages_table"]
        return counts

    def _run_stages(self, data, tables, index, valid, ring,
                    span_args=None):
        """Thread one step through every stage — the ONE
        dispatch idiom shared by tail-prefill waves, chunk waves,
        decode ticks, and the speculative verify forward: per-stage
        device puts, the donated step program with its same-statement
        slab rebind, and per stage a ``sky.serve.put`` span around the
        puts and a dispatch span (``sky.serve.stage``; ``ring`` on the
        stage's lane in the ring).  Returns the last stage's output."""
        sp, eng = self._sp, self._eng_lane
        for k, st in enumerate(self.stages):
            with sp.span("sky.serve.put", eng):
                data = device_put_elided(data, st.device)
                tb = device_put_elided(tables, st.device)
                ix = device_put_elided(index, st.device)
                vl = device_put_elided(valid, st.device)
            with sp.span("sky.serve.stage",
                         sp.lane(st.lane_name, "dispatch"),
                         dict(span_args or (), stage=k), ring=ring):
                data, st.slabs = st._step_donated(
                    st.params, data, st.slabs, tb, ix, vl
                )
        return data

    def _swap_in(self, request: Request) -> bool:
        """Re-seat a swapped-out request: fresh pages, host copies
        scattered back, NO prefill — decoding continues from exactly
        where the swap-out left it.  False (nothing mutated) when the
        pages cannot be charged yet.

        Integrity gate FIRST: the record's swap-out checksum is
        re-computed over the host payload before any state is touched.
        A mismatch means the parked KV is poisoned — the record is
        dropped (``swap_corruptions`` counts it) and the request falls
        back to the recompute-from-prompt path (also returning False,
        with the record gone, so the admission loop re-judges the head
        as a normal recompute re-admission).  A victim whose resume
        prefix has outgrown every bucket cannot recompute either; it
        is FAILED with a reasoned verdict instead of served garbage."""
        record = self._swapped[request.request_id]
        expect = record.get("checksum")
        if expect is not None and _swap_record_checksum(
                record["pages"], record["index"],
                record["data"]) != expect:
            del self._swapped[request.request_id]
            self.stats.swap_corruptions += 1
            tracer = get_tracer()
            if tracer is not None:
                tracer.instant(
                    "swap_corrupt", tracer.lane("serving", "engine"),
                    {"request": request.request_id,
                     "pages": record["pages"]},
                )
            resume_len = int(request.effective_prompt.size)
            try:
                self.bucketer.bucket_for(resume_len)
            except ValueError:
                # structurally unservable: swap was the ONLY way this
                # resume prefix could return, and its record is gone
                self._queue.remove(request)
                request.status = FAILED
                request.fail_reason = (
                    "swap record corrupted and the resume prefix fits "
                    "no bucket"
                )
                self.stats.queue_depth = self._queue.depth
            return False
        pages = self._pool.acquire_pages(
            request.request_id, record["pages"]
        )
        if pages is None:
            return False
        row = self._rows.allocate()
        assert row is not None  # caller checked free rows
        table = np.full(
            (self.max_pages_per_request,), self.num_pages, np.int32
        )
        table[: len(pages)] = pages
        for st, host_pairs in zip(self.stages, record["data"]):
            st.swap_in(table, host_pairs)
        del self._swapped[request.request_id]
        self._queue.remove(request)
        request.slot = row
        request.index = record["index"]
        request.status = RUNNING
        self._running[request.request_id] = request
        self.stats.swap_ins += 1
        self.stats.queue_depth = self._queue.depth
        tracer = get_tracer()
        if tracer is not None:
            now_us = tracer.now()
            tracer.instant(
                "swap_in", tracer.lane("serving", "engine"),
                {"request": request.request_id, "pages": len(pages)},
            )
            self._trace_close_queue(request, tracer, swapped_in=True)
            request.trace_marks["decode"] = now_us
        return True

    def _decode_tick(self) -> None:
        active = list(self._running.values())
        if not active:
            return
        sp, eng = self._sp, self._eng_lane
        ends = [r.index + 1 for r in active]
        width = self._table_width(ends)
        tick_args = {"active": len(active),
                     **self._count_attn_pages(ends, width)}
        with sp.span("sky.serve.decode", eng, tick_args):
            with sp.span("sky.serve.build", eng):
                rows = self.max_concurrency
                sentinel = self.num_pages
                tokens = np.zeros((rows,), np.int32)
                index = np.zeros((rows,), np.int32)
                # inactive rows never write
                valid = np.zeros((rows,), np.int32)
                tables = np.full(
                    (rows, self.max_pages_per_request), sentinel, np.int32
                )
                for r in active:
                    tokens[r.slot] = r.tokens[-1]
                    index[r.slot] = r.index
                    valid[r.slot] = r.index + 1
                    held = self._pool.table(r.request_id)
                    tables[r.slot, : len(held)] = held

                tables = tables[:, :width]
                self._count_quant(index, valid, width, len(active))
            t0 = time.perf_counter()
            compiles0 = xla_compile_count()
            with sp.span("sky.serve.run", eng, tick_args, ring="decode"):
                data = self._run_stages(
                    tokens[:, None], tables, index, valid, "decode"
                )
                logits = data[:, 0]  # [rows, V]
                nxt = _argmax_tokens(logits)
                with sp.span("sky.serve.wait", eng):
                    jax.block_until_ready(nxt)
            now = time.perf_counter()
            self.stats.decode_s += now - t0
            with sp.span("sky.serve.commit", eng):
                self.stats.decode_tokens += len(active)
                self.stats.generated_tokens += len(active)
                self.stats.compiles += xla_compile_count() - compiles0

                nxt_np = np.asarray(nxt)
                sampled = self._sampled_rows(
                    logits, [(r.slot, r) for r in active]
                )
                for r in active:
                    tok = self._pick_token(r, nxt_np[r.slot],
                                           sampled.get(r.slot))
                    r.tokens.append(tok)
                    r.index += 1
                    if r.done:
                        self._finish(r, now)

    def _spec_tick(self) -> None:
        """One speculative decode tick (replaces the plain decode tick
        while ``spec_k > 0``): the draft proposes ``spec_k`` tokens per
        row autoregressively (``Lq=1`` against stage 0's slab prefix),
        then the whole pipeline verifies all ``spec_k + 1`` positions
        in ONE forward (``Lq=spec_k+1`` — a fixed shape, compiled once)
        and greedy acceptance commits the agreed draft prefix plus the
        target's own next token.  The committed stream is the
        non-speculative greedy stream by construction: only the
        target's argmax ever commits.

        Rollback is a watermark truncate: rejected positions' KV sits
        beyond the committed ``index``, masked by ``decode_visibility``
        and rewritten by the next committed forward; page refcounts
        never move (the admission grant already reserved the request's
        worst-case span, so drafting k ahead is pre-charged).
        Temperature-sampling rows ride the same verify forward and
        commit exactly one token from its position-0 logits — the
        identical logits a plain decode tick would produce — so their
        sample streams are untouched (and contribute nothing to the
        draft/accept/rollback counters: they never consume drafts).
        A tick with NO greedy row falls back to the plain decode tick
        — drafting for rows that cannot accept would be pure waste."""
        active = list(self._running.values())
        if not active:
            return
        if all(r.temperature > 0.0 for r in active):
            self._decode_tick()
            return
        k = self.spec_k
        sp, eng = self._sp, self._eng_lane
        # verify writes cap at min(index+k+1, reserve); one table width
        # (covering that bound) serves BOTH the draft loop and the
        # verify forward, so the two stay on one warmed shape set
        width = self._table_width([
            min(r.index + k + 1, int(r.prompt.size) + r.max_new_tokens)
            for r in active
        ])
        tick_args = {
            "active": len(active), "spec_k": k,
            # the verify forward's walk (the draft's k passes run
            # against stage 0's prefix of layers only)
            **self._count_attn_pages(
                [r.index + k + 1 for r in active], width
            ),
        }
        with sp.span("sky.serve.decode", eng, tick_args):
            with sp.span("sky.serve.build", eng):
                rows = self.max_concurrency
                sentinel = self.num_pages
                tokens = np.zeros((rows,), np.int32)
                index0 = np.zeros((rows,), np.int32)
                # inactive rows: 0 -> drop
                reserve = np.zeros((rows,), np.int32)
                tables = np.full(
                    (rows, self.max_pages_per_request), sentinel, np.int32
                )
                for r in active:
                    tokens[r.slot] = r.tokens[-1]
                    index0[r.slot] = r.index
                    reserve[r.slot] = int(r.prompt.size) + r.max_new_tokens
                    held = self._pool.table(r.request_id)
                    tables[r.slot, : len(held)] = held

                valid = np.minimum(index0 + k + 1, reserve)
                tables = tables[:, :width]
                self._count_quant(index0, valid, width, len(active))
                if self.kv_dtype == "int8":
                    # the draft's k Lq=1 passes also quantize (one tail-page
                    # re-quant per kept step per row) and dequantize (one
                    # gathered width per step) — the verify-only count above
                    # would hide roughly half a spec tick's quantization work
                    slots = [r.slot for r in active]
                    kept = np.clip(reserve[slots] - index0[slots], 0, k)
                    self.stats.quantized_pages += int(kept.sum())
                    self.stats.dequant_blocks += k * len(active) * width
            t0 = time.perf_counter()
            compiles0 = xla_compile_count()
            with sp.span("sky.serve.run", eng, tick_args, ring="draft"):
                stage0 = self.stages[0]
                d = self._draft.num_attn
                # --- draft: k sequential Lq=1 steps against stage 0's slab
                # prefix (the draft's KV IS the target's first d layers' KV —
                # prefix-slice sharing, see serving/speculative.py)
                tb0 = device_put_elided(tables, stage0.device)
                # the ENTIRE k-step autoregressive draft is one compiled
                # program (DraftModel.draft_k, k static): one dispatch and one
                # device->host transfer per tick, not k of each
                drafted_dev, new_prefix = self._draft.draft_k(
                    device_put_elided(tokens, stage0.device),
                    stage0.slabs[:d], tb0,
                    device_put_elided(index0, stage0.device),
                    device_put_elided(reserve, stage0.device), k,
                )
                stage0.slabs = list(new_prefix) + stage0.slabs[d:]
                with sp.span("sky.serve.wait", eng):
                    drafted = np.asarray(drafted_dev, dtype=np.int32)
            # --- verify: one Lq=k+1 forward over the whole pipeline
            with sp.span("sky.serve.run", eng, tick_args, ring="decode"):
                verify_in = np.concatenate([tokens[:, None], drafted], axis=1)
                logits3 = self._run_stages(
                    verify_in, tables, index0, valid, "decode"
                )  # [rows, k+1, V]
                target = _argmax_tokens(logits3)  # [rows, k+1]
                with sp.span("sky.serve.wait", eng):
                    jax.block_until_ready(target)
            now = time.perf_counter()
            self.stats.decode_s += now - t0
            with sp.span("sky.serve.commit", eng):
                self.stats.compiles += xla_compile_count() - compiles0

                target_np = np.asarray(target)
                sampled = self._sampled_rows(
                    logits3[:, 0], [(r.slot, r) for r in active]
                )
                committed_total = 0
                for r in active:
                    row = r.slot
                    if r.temperature > 0.0:
                        # position-0 logits == the plain decode tick's logits;
                        # the drafts for this row are discarded (sampling has
                        # no greedy acceptance rule) and never counted —
                        # accept-rate observability describes greedy traffic
                        tok = self._pick_token(
                            r, target_np[row, 0], sampled.get(row)
                        )
                        commit = [tok][: min(1, r.remaining)]
                    else:
                        remaining = r.remaining
                        accepted = greedy_accept_count(
                            drafted[row], target_np[row, :k]
                        )
                        commit = (
                            [int(t) for t in drafted[row, :accepted]]
                            + [int(target_np[row, accepted])]
                        )
                        ncommit = min(len(commit), remaining)
                        commit = commit[:ncommit]
                        # the accept-rate denominator counts only USABLE
                        # proposals: a row whose remaining budget is below k
                        # could never consume the surplus drafts (the fixed
                        # draft shape still computes them), and charging them
                        # would deflate the rate below 1.0 for a PERFECT draft
                        self.stats.draft_tokens += min(k, remaining)
                        self.stats.accepted_draft_tokens += min(
                            accepted, ncommit
                        )
                        # the verify wrote min(k+1, remaining) positions (its
                        # valid cap); a rollback happened iff the committed
                        # watermark stops short of what was written
                        if ncommit < min(k + 1, remaining):
                            self.stats.spec_rollbacks += 1
                    for tok in commit:
                        r.tokens.append(tok)
                    r.index += len(commit)
                    committed_total += len(commit)
                    if r.done:
                        self._finish(r, now)
                self.stats.decode_tokens += committed_total
                self.stats.generated_tokens += committed_total

    @staticmethod
    def _sampled_rows(logits, rows) -> Dict[int, np.ndarray]:
        """Host copies of ONLY the logits rows that temperature
        sampling needs: ``rows`` is (row index, request) pairs; greedy
        requests cost nothing — a full [slots, vocab] device->host pull
        per token would tax every tick for the life of one sampling
        request."""
        need = [i for i, r in rows if r.temperature > 0.0]
        if not need:
            return {}
        pulled = np.asarray(logits[np.asarray(need)])
        return dict(zip(need, pulled))

    def _pick_token(self, request: Request, greedy_tok, logits_row) -> int:
        """Greedy by default; per-request temperature sampling draws
        from a request-local stream (``fold_in(key(seed), position)``)
        so interleaving with other requests never perturbs it."""
        if request.temperature <= 0.0:
            return int(greedy_tok)
        sub = jax.random.fold_in(
            jax.random.key(request.seed),
            int(request.prompt.size) + len(request.tokens),
        )
        return int(
            jax.random.categorical(
                sub,
                jnp.asarray(logits_row, jnp.float32) / request.temperature,
            )
        )


__all__ = ["ServingEngine", "ServingStats"]
