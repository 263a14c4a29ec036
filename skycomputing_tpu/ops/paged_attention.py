"""Fused paged-attention decode kernel (PagedAttention's kernel half).

PR 9 reproduced the *memory-management* half of PagedAttention (Kwon et
al., SOSP '23): refcounted pages, per-request page tables, copy-on-write
prefix sharing.  Its device math, though, still materialized each row's
full virtual KV view in HBM every layer of every decode tick
(``serving/kv_cache.gather_kv_pages``): a ``[R, table_width * page_size,
heads, head_dim]`` gather whose cost scales with the TABLE width, not the
tokens actually live.  This module is the kernel half: the page walk
moves INSIDE a Pallas kernel, so the gathered view never exists —

- grid ``(rows, head groups, table_width)``: each program owns one
  (row, head group) slice of one logical page, a head group being the
  fewest heads whose flattened ``heads * head_dim`` lanes fill a
  128-lane tile (2 at head_dim 64) — a per-head ``(.., 1, head_dim)``
  block is not a shape the TPU lowering accepts.  The page table rides
  scalar prefetch
  (``pltpu.PrefetchScalarGridSpec``) so the K/V BlockSpec index maps
  gather the right PHYSICAL page per grid step — one page-sized block
  through VMEM at a time, the ``flash_attention.py`` streaming recipe
  applied through an indirection table;
- online softmax: running max / running sum / accumulator live in VMEM
  scratch across the page dimension (initialized at page 0, emitted at
  the last page), so the ``[Lq, positions]`` score matrix never hits HBM;
- dead pages cost no math: a page wholly beyond a row's causal bound is
  skipped with ``pl.when`` (its block DMA still issues — bounding the
  TABLE width is the engine's job, see ``ServingEngine`` ``gather_pages``);
- sentinel table entries (``>= num_pages``, the pool's padding) clamp to
  a real page and are masked by the same causal rule that masks a slot
  row's stale tail — by the pool's covering invariant a sentinel only
  ever appears past the row's live span;
- int8 pages dequantize in-kernel: ``k/v_scale`` are the pool's
  per-page-per-head scale slabs (``serving/kv_cache.QuantizedPages``),
  fetched one page's ``[1, heads]`` row at a time by the same table
  indirection and multiplied into the block's matmul results (a scale
  is constant over its block) — the quantized pool never takes an
  HBM-side dequantized copy either.

Off-TPU the kernel runs in interpret mode (the ``flash_attention.py``
convention), which is how the CPU suite pins it against the XLA
reference; ``attn_impl="pallas"`` on a CPU engine is therefore a
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


def _heads_per_block(H: int, D: int) -> int:
    """Heads sharing one lane block of the flattened ``H * D`` axis.

    Mosaic wants the last block dimension to be a multiple of 128 lanes
    or the whole axis, so the smallest legal head group is the smallest
    divisor ``G`` of ``H`` with ``G * D`` a multiple of 128 (2 heads at
    head_dim 64, 1 at 128); a model whose row is not lane-aligned at any
    divisor takes the whole row as one block."""
    for g in range(1, H):
        if H % g == 0 and (g * D) % 128 == 0:
            return g
    return H


def _paged_kernel(table_ref, idx_ref, q_ref, k_ref, v_ref, *rest,
                  page_size: int, head_dim: int, softmax_scale: float,
                  quantized: bool):
    """One (row, head group, logical page) grid cell.

    Blocks are ``[Lq | page_size, G * head_dim]`` slices of the
    flattened heads axis; head ``g`` of the group is isolated by
    zeroing the other heads' lanes of ``q`` (they then add nothing to
    the ``q k^T`` contraction) and by reading only its lanes of the
    ``p v`` product at emit time — elementwise masks and plain 2-D
    matmuls, the shapes Mosaic tiles without relayouts."""
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    r = pl.program_id(0)
    hb = pl.program_id(1)
    i = pl.program_id(2)
    G, Lq, BW = acc_ref.shape

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    idx = idx_ref[r]
    lane_head = jax.lax.broadcasted_iota(jnp.int32, (Lq, BW), 1) // head_dim

    # page i spans positions [i*ps, (i+1)*ps); the row's last query sits
    # at idx + Lq - 1, so later pages hold nothing visible — skipping
    # them also keeps a fully-masked block from feeding exp(-inf+inf)
    # NaNs into the running max
    @pl.when(i * page_size <= idx + Lq - 1)
    def _page():
        q = q_ref[0].astype(jnp.float32) * softmax_scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        pos = i * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (Lq, page_size), 1
        )
        qpos = idx + jax.lax.broadcasted_iota(
            jnp.int32, (Lq, page_size), 0
        )
        visible = pos <= qpos
        if quantized:
            # this page's [1, H] scale rows; head hb*G + g's entry is
            # picked by a lane mask (a dynamic lane index would not
            # lower).  A per-(page, head) scale is constant over the
            # block, so it multiplies the matmul RESULTS
            head_lane = jax.lax.broadcasted_iota(
                jnp.int32, ks_ref.shape[1:], 1
            )
            ks_row, vs_row = ks_ref[0], vs_ref[0]
        for g in range(G):
            s = jax.lax.dot_general(
                jnp.where(lane_head == g, q, 0.0), k,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [Lq, page_size]
            if quantized:
                pick = head_lane == hb * G + g
                s = s * jnp.sum(jnp.where(pick, ks_row, 0.0))
            s = jnp.where(visible, s, -jnp.inf)
            m_prev = m_ref[g]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[g] = l_ref[g] * corr + jnp.sum(p, axis=-1, keepdims=True)
            pv = jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [Lq, BW]; only head g's lanes are read back
            if quantized:
                pv = pv * jnp.sum(jnp.where(pick, vs_row, 0.0))
            acc_ref[g] = acc_ref[g] * corr + pv
            m_ref[g] = m_new

    @pl.when(i == pl.num_programs(2) - 1)
    def _emit():
        out = jnp.zeros((Lq, BW), jnp.float32)
        for g in range(G):
            out = jnp.where(
                lane_head == g,
                acc_ref[g] / jnp.maximum(l_ref[g], 1e-30), out,
            )
        o_ref[0] = out.astype(o_ref.dtype)


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
    speculative verify); ``k_pages``/``v_pages``: [num_pages, page_size,
    H, D] physical page pools — fp, or int8 with ``k_scale``/``v_scale``
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
    R, Lq, H, D = q.shape
    num_pages, page_size = k_pages.shape[0], k_pages.shape[1]
    table_width = page_table.shape[1]
    if softmax_scale is None:
        softmax_scale = float(D) ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    quantized = k_scale is not None
    if quantized != (v_scale is not None):
        raise ValueError("pass k_scale AND v_scale together (int8) "
                         "or neither (fp)")

    idx = jnp.broadcast_to(
        jnp.reshape(jnp.asarray(index, jnp.int32), (-1,)), (R,)
    )
    table = jnp.asarray(page_table, jnp.int32)
    G = _heads_per_block(H, D)
    BW = G * D

    def q_map(r, hb, i, table_ref, idx_ref):
        return (r, 0, hb)

    def kv_map(r, hb, i, table_ref, idx_ref):
        # sentinel entries clamp into the pool; their positions are past
        # the row's causal bound by the pool's covering invariant, so
        # the mask (not the clamp target) is what keeps them inert
        return (jnp.minimum(table_ref[r, i], num_pages - 1), 0, hb)

    def scale_map(r, hb, i, table_ref, idx_ref):
        return (jnp.minimum(table_ref[r, i], num_pages - 1), 0, 0)

    # heads flatten into the lane axis (free reshapes: H and D are the
    # trailing, contiguous dims), so a block's last two dims are
    # (Lq | page_size, G*D): whole-axis x lane-aligned — a (.., 1, D)
    # per-head block is not a shape the TPU lowering accepts
    in_specs = [
        pl.BlockSpec((1, Lq, BW), q_map),
        pl.BlockSpec((1, page_size, BW), kv_map),
        pl.BlockSpec((1, page_size, BW), kv_map),
    ]
    operands = [
        q.reshape(R, Lq, H * D),
        k_pages.reshape(num_pages, page_size, H * D),
        v_pages.reshape(num_pages, page_size, H * D),
    ]
    if quantized:
        in_specs += [pl.BlockSpec((1, 1, H), scale_map)] * 2
        operands += [
            jnp.reshape(k_scale, (num_pages, 1, H)),
            jnp.reshape(v_scale, (num_pages, 1, H)),
        ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(R, H // G, table_width),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Lq, BW), q_map),
        scratch_shapes=[
            pltpu.VMEM((G, Lq, 1), jnp.float32),  # running max
            pltpu.VMEM((G, Lq, 1), jnp.float32),  # running sum
            pltpu.VMEM((G, Lq, BW), jnp.float32),  # output accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_kernel, page_size=page_size, head_dim=D,
            softmax_scale=softmax_scale, quantized=quantized,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, Lq, H * D), q.dtype),
        interpret=interpret,
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
        flat = slab.reshape((num_pages * page_size,) + slab.shape[2:])
        out = flat[flat_pos].astype(jnp.float32)
        if scale is not None:
            page_of = flat_pos // page_size
            out = out * scale[page_of][:, :, :, None]
        return out

    k_virt = gather(k_pages, k_scale)  # [R, W*ps, H, D]
    v_virt = gather(v_pages, v_scale)
    s = jnp.einsum(
        "blhd,bmhd->bhlm", q.astype(jnp.float32) * softmax_scale, k_virt
    )
    virt_len = k_virt.shape[1]
    qpos = idx[:, None] + jnp.arange(Lq, dtype=jnp.int32)
    kpos = jnp.arange(virt_len, dtype=jnp.int32)
    visible = kpos[None, None, :] <= qpos[:, :, None]
    s = jnp.where(visible[:, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhlm,bmhd->blhd", p, v_virt).astype(q.dtype)


__all__ = ["paged_attention", "paged_attention_reference"]
