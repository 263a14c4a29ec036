"""Continuous-batching inference over the load-balanced MPMD pipeline.

The training side of this repo reproduces the paper's contribution —
profile-driven layer->device allocation for heterogeneous pipelines;
this package is the serving side the ROADMAP's north star demands:

- :mod:`.kv_cache` — the KV-cache device math: the engine's page
  pools (``[num_pages, page_size, heads * head_dim]`` gather/scatter
  through page tables; :class:`QuantizedPages` stores them int8 with
  per-page-per-head scale slabs, quantized at write time) and the
  row-per-sequence slabs of ``models/gpt.py``'s single-request
  reference decoder, donation-friendly in-place updates throughout —
  the fused decode kernel that walks page tables in-kernel lives in
  ``ops/paged_attention.py`` and is engine-selected via ``attn_impl=``;
- :mod:`.paging` — the page pool's host bookkeeping (pure stdlib):
  free-list page allocator with refcounts and copy-on-write grants,
  radix prefix index for compute-once shared prompts, decode-row
  ledger, swap-vs-recompute preemption policy;
- :mod:`.batcher` — shape-bucketing admission (prompt lengths padded to
  a small fixed bucket set so steady-state decode compiles once);
- :mod:`.engine` — :class:`ServingEngine`, iteration-level continuous
  batching (Orca-style: requests join/leave the running batch between
  decode steps) from one page pool over pipeline stages placed by the
  allocator, with :class:`ServingStats` SLO metrics; ``prefill_chunk=``
  interleaves budgeted prefill chunks with decode ticks, ``spec_k=``
  layers draft-model speculative decoding on top;
- :mod:`.speculative` — :class:`DraftModel`, the prefix-slice draft
  (shares the target's stage-0 params and page slabs) plus the greedy
  acceptance rule;
- :mod:`.profile` — :class:`DecodeModelBenchmarker`, the decode-step
  cost/memory profile that makes ``Allocator.serving_allocate`` produce
  serving-balanced partitions instead of reusing training costs.

(``models/gpt.py``'s decode paths import ``kv_cache`` function-locally,
so the models -> serving edge never executes at import time and the
package can import its submodules eagerly without a cycle.)
"""

from __future__ import annotations

from .batcher import (
    AdmissionQueue,
    QueueFullError,
    Request,
    ShapeBucketer,
)
from .engine import ServingEngine, ServingStats
from .kv_cache import (
    KVCacheSpec,
    QuantizedPages,
    gather_kv_pages,
    init_layer_caches,
    init_paged_caches,
    kv_mb_per_layer,
    kv_spec_from_config,
    paged_kv_mb_per_layer,
    paged_update_kv,
    quantize_pages,
    update_kv_cache,
)
from .paging import (
    ChunkBudgetPolicy,
    KV_DTYPE_ITEMSIZE,
    PagedKVCachePool,
    RadixPrefixIndex,
    RowAllocator,
    choose_preempt_mode,
    paged_pool_mb,
    pages_for,
    pages_per_mb,
)
from .profile import DecodeModelBenchmarker
from .speculative import DraftModel, greedy_accept_count

__all__ = [
    "AdmissionQueue",
    "ChunkBudgetPolicy",
    "DecodeModelBenchmarker",
    "DraftModel",
    "KVCacheSpec",
    "KV_DTYPE_ITEMSIZE",
    "PagedKVCachePool",
    "QuantizedPages",
    "QueueFullError",
    "RadixPrefixIndex",
    "Request",
    "RowAllocator",
    "ServingEngine",
    "ServingStats",
    "ShapeBucketer",
    "choose_preempt_mode",
    "gather_kv_pages",
    "greedy_accept_count",
    "init_layer_caches",
    "init_paged_caches",
    "kv_mb_per_layer",
    "kv_spec_from_config",
    "paged_kv_mb_per_layer",
    "paged_pool_mb",
    "paged_update_kv",
    "pages_for",
    "pages_per_mb",
    "quantize_pages",
    "update_kv_cache",
]
