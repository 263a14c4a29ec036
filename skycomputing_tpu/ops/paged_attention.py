"""Fused paged-attention decode kernel (PagedAttention's kernel half).

PR 9 reproduced the *memory-management* half of PagedAttention (Kwon et
al., SOSP '23): refcounted pages, per-request page tables, copy-on-write
prefix sharing.  Its device math, though, still materialized each row's
full virtual KV view in HBM every layer of every decode tick
(``serving/kv_cache.gather_kv_pages``): a ``[R, table_width * page_size,
heads * head_dim]`` gather whose cost scales with the TABLE width, not the
tokens actually live.  This module is the kernel half: the page walk
moves INSIDE a Pallas kernel, so the gathered view never exists —

- grid ``(rows, query blocks)``: one step owns a row's query block
  and WALKS the row's pages itself.  K and V stay in HBM
  (``memory_space=pl.ANY``); the page table rides scalar prefetch
  (``pltpu.PrefetchScalarGridSpec``), and a step copies
  ``pages_per_step`` PHYSICAL pages at a time (``make_async_copy``,
  ids from the table) into one of two VMEM slots, the next chunk in
  flight while this one is computed (the next row's first chunk under
  this row's last).  A chunk holds whole ``H * D``
  rows — a whole-axis last dimension is a legal block at any width —
  which is the shape the pool is STORED in (``serving/kv_cache.
  init_paged_caches``: ``[num_pages, page_size, H * D]``), so the
  slabs reach the call untouched.  A ``[.., H, D]`` pool reshaped
  here would NOT be free: on a TPU the last two axes are tiled, the
  two shapes tile the same bytes differently, and XLA copies every
  slab, whatever is live.  ``pages_per_step`` is reckoned from the
  page's bytes against a VMEM budget (8 bf16 pages of 40 KB at
  GPT-2-large), so bytes set the pace and not grid steps;
- live pages only: a block's walk ends at ``ceil((index + its last
  query + 1) / page_size)`` pages.  A page past that is neither
  fetched nor stepped, so a row costs what it holds and not the
  table's width (bounding the TABLE still bounds the scalar prefetch
  and the XLA reference, see ``ServingEngine._table_width``);
- heads by shape: heads are taken ``G`` at a time as the lane-aligned
  slice of the chunk they share (``G * D`` a multiple of 128, or the
  whole row), their queries stacked on the rows with the other heads'
  lanes zeroed, so a slice is two plain 2-D matmuls whatever ``Lq``
  is.  ``G`` is the widest slice whose ``G * query rows`` stays
  within 256: the whole row of heads for a decode tick's one query
  (one ``[H, H*D] x [T, H*D]^T`` and one ``[H, T] x [T, H*D]`` a
  chunk), a head pair for a prefill block of 128 queries.  ``Lq``
  itself is tiled (a grid axis over query blocks, each to its own
  causal bound) where a whole block's state would not fit VMEM.  One
  algorithm; ``Lq``, ``H * D``, ``page_size`` and the dtypes pick its
  block sizes;
- online softmax: running max / running sum / accumulator live in VMEM
  scratch across the walk, float32 throughout, so the ``[Lq,
  positions]`` score matrix never hits HBM.  bfloat16 q and pages
  meet in the MXU as they are (their products are exact in float32);
  the float32 weights meet bfloat16 values as their three bfloat16
  pieces, so nothing is rounded that the float32 reference keeps;
- sentinel table entries (``>= num_pages``, the pool's padding) lie
  past a row's live span by the pool's covering invariant, so the
  walk never reaches them; the clamp on the page id is a safeguard.
  A chunk's unfetched tail and a live page's stale tail are masked by
  the one causal rule;
- int8 pages dequantize in-kernel: ``k/v_scale`` are the pool's
  per-page-per-head scale slabs (``serving/kv_cache.QuantizedPages``).
  The table's ``[R, table_width]`` scale rows are gathered beside the
  call (small rows, never pages) and laid out by chunk position; a
  scale is constant over its page's columns of a head's rows, so it
  multiplies the scores, and the weights before they meet the int8
  values — the quantized pool never takes an HBM-side dequantized
  copy either.

Off-TPU the kernel runs in Pallas' TPU interpret mode
(``pltpu.InterpretParams``: the copies, semaphores and scratch are
simulated, fresh buffers hold NaN), which is how the CPU suite pins it
against the XLA reference; ``attn_impl="pallas"`` on a CPU engine is therefore a
correctness surface, not a fast path — the compiled kernel needs a TPU
(``tests/test_tpu_compile.py`` compiles it for a described v5e).

Layer discipline: this module speaks raw arrays only (q, slabs, tables,
scales) — the serving package's pool/grant types stay out of ``ops``;
``models/gpt.decode_paged`` unpacks them before calling in.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _heads_per_block(H: int, D: int, query_rows: int) -> int:
    """Heads whose queries one matmul takes together, stacked on the
    rows against the lane slice of the chunk those heads share.

    A slice must be lane-aligned: ``G`` divides ``H`` and ``G * D`` is a
    multiple of 128 (2 heads at head_dim 64, 1 at 128), or the slice is
    the whole row (the narrow test models).  Every head of a slice meets
    the slice's full width, its own lanes apart from zeros, so a wider
    slice costs the MXU more rows and the kernel fewer, larger matmuls:
    the widest whose ``G * query_rows`` stacked rows stay within
    ``_MAX_ROWS`` is taken, the whole row for a decode tick's one
    query and a head pair for a prefill block of 128."""
    aligned = [g for g in range(1, H + 1)
               if H % g == 0 and (g * D) % 128 == 0]
    if not aligned:
        return H
    fits = [g for g in aligned if g * query_rows <= _MAX_ROWS]
    return max(fits) if fits else min(aligned)


# VMEM the blocking is reckoned against: the double-buffered K and V
# chunks, and the state a query row carries (stacked q, running max and
# sum, accumulator, its share of the q and output blocks).  Together
# they stay inside the 16 MiB a kernel may use on a v5e unasked.
_KV_VMEM_BYTES = 3 << 19
_Q_VMEM_BYTES = 8 << 20
_MAX_CHUNK_TOKENS = 256
_MAX_ROWS = 256  # query rows to a step, and stacked rows to a matmul


def _floor_pow2(n: int) -> int:
    return 1 << (max(int(n), 1).bit_length() - 1)


def _pages_per_step(page_size: int, row_bytes: int, table_width: int) -> int:
    """Physical pages one step copies into VMEM: the largest power of
    two whose four buffers (K and V, two slots each) fit the budget,
    held to ``_MAX_CHUNK_TOKENS`` positions and to the table's width."""
    fit = _KV_VMEM_BYTES // (4 * page_size * row_bytes)
    return _floor_pow2(
        min(fit, _MAX_CHUNK_TOKENS // page_size, table_width)
    )


def _query_rows_per_step(Lq: int, H: int, D: int, itemsize: int) -> int:
    """Query rows one grid step owns: all of ``Lq`` where their state
    fits the budget, else the largest power of two that does (a grid
    axis then walks the query blocks; the last may be partial)."""
    # a block that needs tiling stacks the narrowest slice; per head and
    # query row: float32 accumulator and (at most float32) stacked q over
    # the slice's lanes, running max and sum padded to a lane tile each,
    # and two buffers each of the q and output blocks
    lanes = -(-_heads_per_block(H, D, _MAX_ROWS) * D // 128) * 128
    per_row = H * (2 * lanes * 4 + 2 * 128 * 4) + 4 * H * D * itemsize
    cap = min(_MAX_ROWS, _Q_VMEM_BYTES // per_row)
    return Lq if Lq <= cap else max(8, _floor_pow2(cap))


def _paged_kernel(table_ref, idx_ref, q_ref, k_hbm, v_hbm, *rest,
                  page_size: int, head_dim: int, heads_per_block: int,
                  pages_per_step: int, query_len: int,
                  softmax_scale: float, quantized: bool):
    """One (row, query block) grid step: walk the row's LIVE pages,
    ``pages_per_step`` at a time.

    K and V stay in HBM; a chunk's physical pages (ids from the
    scalar-prefetched table) are copied into one of two VMEM slots, the
    next chunk in flight while this one is computed, the next STEP's
    first chunk under this step's last.  A chunk holds
    whole ``H * D`` rows; heads are taken ``G`` at a time as the
    lane-aligned slice they share, the ``G`` heads' queries stacked
    along the rows with the other heads' lanes zeroed, so a slice costs
    two plain 2-D matmuls (``[G*Tq, G*D] x [T, G*D]^T`` and ``[G*Tq, T]
    x [T, G*D]``) whatever ``Lq`` is."""
    if quantized:
        ks_ref, vs_ref, o_ref, *scratch = rest
    else:
        o_ref, *scratch = rest
    k_buf, v_buf, sem, slot_ref, qs_ref, m_ref, l_ref, acc_ref = scratch
    r = pl.program_id(0)
    j = pl.program_id(1)
    G, D, ps, pps = heads_per_block, head_dim, page_size, pages_per_step
    NG, M, BW = acc_ref.shape  # M: G * Tq stacked rows, padded to 8
    Tq = q_ref.shape[1]
    T = pps * ps
    num_pages = k_hbm.shape[0]
    table_width = table_ref.shape[1]
    compute_dtype = qs_ref.dtype

    def live_pages(r, j):
        # the block's last query sits at index + q_end - 1: pages past it
        # hold nothing any of its rows can see, and are neither fetched
        # nor stepped.  (Sentinel entries lie past that bound by the
        # pool's covering invariant; the clamp on the page id below is a
        # safeguard, not a path.)
        q_end = jnp.minimum((j + 1) * Tq, query_len)
        return jnp.clip((idx_ref[r] + q_end + ps - 1) // ps, 1, table_width)

    def chunk_dmas(r, n_live, c, slot, act):
        """``act`` on the K and V copy of each live page of chunk ``c``
        of row ``r``: started and waited for under the same condition."""
        for p in range(pps):
            page = c * pps + p

            @pl.when(page < n_live)
            def _(page=page, p=p):
                phys = jnp.minimum(table_ref[r, page], num_pages - 1)
                act(pltpu.make_async_copy(
                    k_hbm.at[phys], k_buf.at[slot, p], sem.at[0, slot]))
                act(pltpu.make_async_copy(
                    v_hbm.at[phys], v_buf.at[slot, p], sem.at[1, slot]))

    def start_chunk(*chunk):
        chunk_dmas(*chunk, lambda dma: dma.start())

    def wait_chunk(*chunk):
        chunk_dmas(*chunk, lambda dma: dma.wait())

    idx = idx_ref[r]
    n_live = live_pages(r, j)
    n_chunks = (n_live + pps - 1) // pps
    # the step after this one (query blocks run fastest): its first chunk
    # is started under this step's last, so only the call's very first
    # chunk is waited for with nothing to compute
    last_block = j + 1 == pl.num_programs(1)
    r_next = jnp.where(last_block, r + 1, r)
    j_next = jnp.where(last_block, 0, j + 1)
    has_next = r_next < pl.num_programs(0)
    r_next = jnp.minimum(r_next, pl.num_programs(0) - 1)

    @pl.when((r == 0) & (j == 0))
    def _first():
        # a chunk's unfetched tail is masked, but its weight of 0 times
        # whatever a fresh VMEM buffer holds must stay 0: after this the
        # slots only ever hold pool pages, live or stale
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        slot_ref[0] = 0
        start_chunk(r, n_live, 0, 0)

    slot0 = slot_ref[0]  # where this step's first chunk is, or will be

    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    # row g*Tq + i of a stacked block is query i of the slice's head g,
    # which owns lanes [g*D, (g+1)*D) of the slice
    lane_head = jax.lax.broadcasted_iota(jnp.int32, (Tq, BW), 1) // D
    if M > G * Tq:
        qs_ref[...] = jnp.zeros_like(qs_ref)
    for hb in range(NG):
        q = q_ref[0, :, hb * BW:(hb + 1) * BW].astype(jnp.float32)
        for g in range(G):
            qs_ref[hb, g * Tq:(g + 1) * Tq, :] = jnp.where(
                lane_head == g, q, 0.0
            ).astype(compute_dtype)

    row = jax.lax.broadcasted_iota(jnp.int32, (M, T), 0)
    row_head = jnp.zeros((M, T), jnp.int32)
    for g in range(1, G):
        row_head = row_head + (row >= g * Tq).astype(jnp.int32)
    qpos = idx + j * Tq + row - Tq * row_head
    col = jax.lax.broadcasted_iota(jnp.int32, (M, T), 1)

    def chunk(c, carry):
        slot = (slot0 + c) % 2

        @pl.when(c + 1 < n_chunks)
        def _():
            start_chunk(r, n_live, c + 1, 1 - slot)

        @pl.when((c + 1 == n_chunks) & has_next)
        def _():
            slot_ref[0] = 1 - slot
            start_chunk(r_next, live_pages(r_next, j_next), 0, 1 - slot)

        wait_chunk(r, n_live, c, slot)
        visible = c * T + col <= qpos
        for hb in range(NG):
            lanes = slice(hb * BW, (hb + 1) * BW)
            k = k_buf[slot, :, :, lanes].reshape(T, BW)
            v = v_buf[slot, :, :, lanes].reshape(T, BW)
            s = _dot(qs_ref[hb], k.astype(compute_dtype), trans_b=True)
            s = s * softmax_scale  # [M, T]
            if quantized:
                # a per-(page, head) scale is constant over its page's
                # columns of a head's rows: it multiplies the scores,
                # and the weights before they meet the int8 values
                k_scale = jnp.zeros((M, T), jnp.float32)
                v_scale = jnp.zeros((M, T), jnp.float32)
                for g in range(G):
                    h = hb * G + g
                    k_scale = jnp.where(
                        row_head == g, ks_ref[0, c, h:h + 1, :], k_scale
                    )
                    v_scale = jnp.where(
                        row_head == g, vs_ref[0, c, h:h + 1, :], v_scale
                    )
                s = s * k_scale
            s = jnp.where(visible, s, -jnp.inf)
            m_prev = m_ref[hb]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[hb] = l_ref[hb] * corr + jnp.sum(p, axis=-1, keepdims=True)
            if quantized:
                p = p * v_scale
            pv = _dot_f32_lhs(p, v.astype(compute_dtype))  # [M, BW]
            acc_ref[hb] = acc_ref[hb] * corr + pv
            m_ref[hb] = m_new
        return carry

    jax.lax.fori_loop(0, n_chunks, chunk, 0)

    for hb in range(NG):
        out = jnp.zeros((Tq, BW), jnp.float32)
        for g in range(G):
            rows = slice(g * Tq, (g + 1) * Tq)
            out = jnp.where(
                lane_head == g,
                acc_ref[hb, rows, :] / jnp.maximum(l_ref[hb, rows, :], 1e-30),
                out,
            )
        o_ref[0, :, hb * BW:(hb + 1) * BW] = out.astype(o_ref.dtype)


def _dot(a, b, *, trans_b: bool = False):
    """``a @ b`` (or ``a @ b^T``) accumulated in float32.  bfloat16
    operands multiply exactly into float32; float32 operands take the
    MXU's full-precision passes."""
    return jax.lax.dot_general(
        a, b, (((1,), (1 if trans_b else 0,)), ((), ())),
        precision=(jax.lax.Precision.HIGHEST
                   if a.dtype == jnp.float32 else None),
        preferred_element_type=jnp.float32,
    )


def _dot_f32_lhs(p, v):
    """``p @ v`` for float32 weights ``p`` (rows a multiple of 8).
    Against bfloat16 values the weights go through the MXU as their
    three bfloat16 pieces (together all 24 bits of the mantissa), stacked
    on the rows of one matmul, each product exact and summed in float32:
    the float32 result without a float32 copy of ``v``."""
    if v.dtype == jnp.float32:
        return _dot(p, v)
    rows = p.shape[0]
    pieces = []
    for _ in range(3):
        pieces.append(p.astype(v.dtype).astype(jnp.float32))
        p = p - pieces[-1]
    out = _dot(jnp.concatenate(pieces, axis=0).astype(v.dtype), v)
    return out[:rows] + out[rows:2 * rows] + out[2 * rows:]


def _scales_by_position(scale, table, pages_per_step: int, page_size: int):
    """``[num_pages, H]`` scales -> ``[R, chunks, H, pages_per_step *
    page_size]``: each table entry's scale row, laid out as the kernel's
    chunks see it (one value per position of the chunk, heads on the
    sublanes).  A gather of ``R x table_width`` small rows, not of the
    pool."""
    R, width = table.shape
    chunks = -(-width // pages_per_step)
    table = jnp.pad(table, ((0, 0), (0, chunks * pages_per_step - width)))
    rows = scale[jnp.minimum(table, scale.shape[0] - 1)]  # [R, W', H]
    rows = jnp.repeat(jnp.swapaxes(rows, 1, 2), page_size, axis=2)
    rows = rows.reshape(R, scale.shape[1], chunks, -1)
    return jnp.swapaxes(rows, 1, 2).astype(jnp.float32)


def paged_attention(
    q,
    k_pages,
    v_pages,
    page_table,
    index,
    *,
    k_scale=None,
    v_scale=None,
    softmax_scale: Optional[float] = None,
    interpret: Optional[bool] = None,
):
    """Fused attention over paged KV, table walk inside the kernel.

    ``q``: [R, Lq, H, D] query block (``Lq = 1`` decode, ``Lq = k + 1``
    speculative verify, a whole prompt bucket in prefill);
    ``k_pages``/``v_pages``: [num_pages, page_size,
    H * D] physical page pools, heads merged into the last axis as the
    cache manager stores them — fp, or int8 with ``k_scale``/``v_scale``
    [num_pages, H] per-page-per-head dequant scales; ``page_table``:
    [R, table_width] int32 logical->physical, sentinel-padded
    (``>= num_pages`` entries clamp and are causally masked);
    ``index``: [R] (or scalar) position of each row's FIRST query —
    query ``j`` sits at ``index + j`` and sees positions ``<= index + j``.

    Returns the attention context [R, Lq, H, D] in ``q``'s dtype.  The
    math is the XLA reference's (``float32`` softmax, same causal/
    staleness mask) restructured as online softmax, so fp outputs agree
    to float32 roundoff and greedy decode streams are token-identical.

    ``interpret=None`` compiles on a TPU backend and interprets (slow
    but exact) everywhere else — the convenience the CPU suite runs on;
    a TPU run therefore never interprets unless asked to.
    """
    if softmax_scale is None:
        softmax_scale = float(q.shape[-1]) ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if (k_scale is None) != (v_scale is None):
        raise ValueError("pass k_scale AND v_scale together (int8) "
                         "or neither (fp)")
    return _paged_attention(
        q, k_pages, v_pages, page_table, index, k_scale, v_scale,
        softmax_scale=float(softmax_scale), interpret=bool(interpret),
    )


@functools.partial(jax.jit, static_argnames=("softmax_scale", "interpret"))
def _paged_attention(q, k_pages, v_pages, page_table, index, k_scale,
                     v_scale, *, softmax_scale: float, interpret: bool):
    """The call itself, jitted so that a program's many calls at one
    shape (a layer each) are traced and lowered once, not once a layer:
    tracing the kernel costs a few tenths of a second."""
    R, Lq, H, D = q.shape
    num_pages, page_size = k_pages.shape[0], k_pages.shape[1]
    table_width = page_table.shape[1]
    quantized = k_scale is not None
    idx = jnp.broadcast_to(jnp.reshape(index.astype(jnp.int32), (-1,)), (R,))
    table = page_table.astype(jnp.int32)
    HD = H * D
    pps = _pages_per_step(page_size, HD * k_pages.dtype.itemsize, table_width)
    Tq = _query_rows_per_step(Lq, H, D, q.dtype.itemsize)
    G = _heads_per_block(H, D, Tq)
    BW = G * D
    M = -(-G * Tq // 8) * 8  # stacked rows, a whole number of sublane tiles
    # bfloat16 (and int8, exactly bfloat16) pages meet a bfloat16 q as
    # they are; anything else is multiplied in float32
    as_stored = q.dtype == jnp.bfloat16 and k_pages.dtype in (
        jnp.bfloat16, jnp.int8)
    compute_dtype = jnp.bfloat16 if as_stored else jnp.float32

    def q_map(r, j, table_ref, idx_ref):
        return (r, j, 0)

    # the pools go in as they are stored; only q (an activation) has
    # its heads merged into the lane axis here.  A whole-axis last
    # dimension is a legal block at any width
    in_specs = [
        pl.BlockSpec((1, Tq, HD), q_map),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    operands = [q.reshape(R, Lq, HD), k_pages, v_pages]
    if quantized:
        scales = [
            _scales_by_position(s, table, pps, page_size)
            for s in (k_scale, v_scale)
        ]
        in_specs += [
            pl.BlockSpec((1,) + scales[0].shape[1:],
                         lambda r, j, table_ref, idx_ref: (r, 0, 0, 0))
        ] * 2
        operands += scales

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(R, -(-Lq // Tq)),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Tq, HD), q_map),
        scratch_shapes=[
            pltpu.VMEM((2, pps, page_size, HD), k_pages.dtype),
            pltpu.VMEM((2, pps, page_size, HD), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),  # slot of the step's first chunk
            pltpu.VMEM((H // G, M, BW), compute_dtype),  # stacked q
            pltpu.VMEM((H // G, M, 1), jnp.float32),  # running max
            pltpu.VMEM((H // G, M, 1), jnp.float32),  # running sum
            pltpu.VMEM((H // G, M, BW), jnp.float32),  # accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_kernel, page_size=page_size, head_dim=D,
            heads_per_block=G, pages_per_step=pps, query_len=Lq,
            softmax_scale=softmax_scale, quantized=quantized,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, Lq, HD), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=pltpu.InterpretParams() if interpret else False,
        # the custom call's name in a device trace (``%decode_paged_
        # attention.<n> = ... custom-call``): without one the jit above
        # would put its own in place of the caller's scope
        # (``GptBlock_Attn.decode_paged``), which the benchmark's
        # ``paged_attn_pct.serve`` found the kernel by
        name="decode_paged_attention",
    )(table, idx, *operands)
    return out.reshape(R, Lq, H, D)


def paged_attention_reference(
    q, k_pages, v_pages, page_table, index, *,
    k_scale=None, v_scale=None, softmax_scale: Optional[float] = None,
):
    """Plain-XLA reference with the kernel's exact contract: gather the
    virtual views (materialized — the cost the kernel removes), mask,
    float32 softmax.  The correctness anchor for the kernel tests and
    the CI smoke; the serving engine's ``attn_impl="xla"`` path computes
    the same thing through ``serving/kv_cache.gather_kv_pages``."""
    R, Lq, H, D = q.shape
    num_pages, page_size = k_pages.shape[0], k_pages.shape[1]
    if softmax_scale is None:
        softmax_scale = float(D) ** -0.5
    idx = jnp.broadcast_to(
        jnp.reshape(jnp.asarray(index, jnp.int32), (-1,)), (R,)
    )
    pos = (
        jnp.asarray(page_table, jnp.int32)[:, :, None] * page_size
        + jnp.arange(page_size, dtype=jnp.int32)[None, None, :]
    )
    flat_pos = jnp.clip(pos.reshape(R, -1), 0, num_pages * page_size - 1)

    def gather(slab, scale):
        flat = slab.reshape(num_pages * page_size, H * D)
        out = flat[flat_pos].astype(jnp.float32).reshape(R, -1, H, D)
        if scale is not None:
            page_of = flat_pos // page_size
            out = out * scale[page_of][:, :, :, None]
        return out

    k_virt = gather(k_pages, k_scale)  # [R, W*ps, H, D]
    v_virt = gather(v_pages, v_scale)
    # float32 on every backend: a TPU's default matmul precision rounds
    # float32 operands to bfloat16, which the kernel does not
    s = jnp.einsum(
        "blhd,bmhd->bhlm", q.astype(jnp.float32) * softmax_scale, k_virt,
        precision=jax.lax.Precision.HIGHEST,
    )
    virt_len = k_virt.shape[1]
    qpos = idx[:, None] + jnp.arange(Lq, dtype=jnp.int32)
    kpos = jnp.arange(virt_len, dtype=jnp.int32)
    visible = kpos[None, None, :] <= qpos[:, :, None]
    s = jnp.where(visible[:, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum(
        "bhlm,bmhd->blhd", p, v_virt, precision=jax.lax.Precision.HIGHEST
    ).astype(q.dtype)


__all__ = ["paged_attention", "paged_attention_reference"]
