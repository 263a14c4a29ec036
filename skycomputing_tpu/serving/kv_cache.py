"""KV-cache device math: the one place attention keys/values are
written and read back.

Two fixed-shape stores share this module.  ``models/gpt.py``'s
``CachedGptDecoder``/``generate_cached`` — the single-request
reference the serving tests compare token streams with — keeps
row-per-sequence slabs ``[batch, max_len, heads, head_dim]``
(:func:`init_layer_caches`, :func:`update_kv_cache`).  The
continuous-batching ``ServingEngine`` keeps page pools ``[num_pages,
page_size, heads * head_dim]`` addressed through per-request page
tables (:func:`init_paged_caches`, :func:`paged_update_kv`,
:func:`gather_kv_pages`; the host bookkeeping is ``serving/paging.py``).
Both hold the same invariants:

- **fixed shapes**: a store is preallocated once; a request joining or
  leaving the batch never changes a compiled program's signature (the
  SKY002 recompile discipline applied to serving);
- **in-place, donation-friendly updates**: :func:`update_kv_cache` is a
  ``dynamic_update_slice`` (scalar index) or a vmapped per-row one
  (per-row index vector) and :func:`paged_update_kv` a scatter through
  a bitcast view, so a caller that donates the store and rebinds to
  the output lets XLA reuse the buffer instead of copying it every
  token;
- **masked staleness**: positions at or beyond a row's current index
  hold stale garbage by design; :func:`decode_visibility` masks them
  out of attention, so a freed row or page can be handed to a new
  request without any zeroing pass.

No model imports here: ``models/gpt.py`` depends on this module (its
``decode`` methods call the update/visibility helpers), not the other
way around.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp


# --------------------------------------------------------------------------
# cache math (used inside jitted layer code)
# --------------------------------------------------------------------------


def update_kv_cache(k_cache, v_cache, k_new, v_new, index):
    """Write ``k_new``/``v_new`` into the caches at per-row positions.

    ``k_cache``/``v_cache``: [B, max_len, heads, head_dim] slabs;
    ``k_new``/``v_new``: [B, Lq, heads, head_dim]; ``index``: either a
    scalar (all rows write at the same offset — the single-request
    decode path) or a [B] vector (each row writes at its own offset,
    every row at a different sequence position).  Returns the updated
    ``(k_cache, v_cache)``.  Out-of-range indices clamp
    (``dynamic_update_slice`` semantics), so an inactive row carried
    through a full-slab decode step can never write outside itself.
    """
    k_new = k_new.astype(k_cache.dtype)
    v_new = v_new.astype(v_cache.dtype)
    if jnp.ndim(index) == 0:
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, k_new, (0, index, 0, 0)
        )
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, v_new, (0, index, 0, 0)
        )
        return k_cache, v_cache

    def row(cache, new, i):
        return jax.lax.dynamic_update_slice(cache, new, (i, 0, 0))

    k_cache = jax.vmap(row)(k_cache, k_new, index)
    v_cache = jax.vmap(row)(v_cache, v_new, index)
    return k_cache, v_cache


def decode_visibility(index, query_len: int, max_len: int):
    """Causal visibility mask for incremental decode: [B|1, Lq, max_len].

    Query position ``q`` of row ``b`` sits at absolute position
    ``index[b] + q`` and may attend to cache positions ``<=`` it.
    Stale garbage beyond a row's current length is strictly in the
    future, so this one mask both enforces causality and hides freed
    slots' leftovers.  ``index`` scalar -> leading axis 1 (broadcasts
    over the batch); ``index`` [B] -> per-row masks.
    """
    q_pos = jnp.reshape(index, (-1, 1)) + jnp.arange(
        query_len, dtype=jnp.int32
    )
    k_pos = jnp.arange(max_len, dtype=jnp.int32)
    return k_pos[None, None, :] <= q_pos[:, :, None]


def decode_positions(index, query_len: int):
    """Absolute positions [B|1, Lq] of the query tokens (for wpe)."""
    return jnp.reshape(index, (-1, 1)) + jnp.arange(
        query_len, dtype=jnp.int32
    )


# --------------------------------------------------------------------------
# paged cache math (used inside jitted layer code; host bookkeeping —
# the allocator, refcounts, radix prefix index — lives in serving/paging.py)
# --------------------------------------------------------------------------


class QuantizedPages(NamedTuple):
    """An int8 page slab with its per-page-per-head dequant scales.

    ``values``: [num_pages, page_size, heads * head_dim] int8 (heads
    and head_dim merged on the last axis, as the kernel reads a page);
    ``scale``: [num_pages, heads] float32 — the parallel *scale slab*.
    One symmetric amax scale covers a (page, head) tile: dequantized
    value = ``values * scale`` with the scale repeated over its head's
    ``head_dim`` lanes.  A NamedTuple so it rides jit/pytree
    plumbing (donation, device_put, scatter/gather helpers) exactly
    like a plain slab array; every paged-math entry point here
    dispatches on this type, so ``kv_dtype="int8"`` changes no caller
    signatures.
    """

    values: jax.Array
    scale: jax.Array


def quantize_pages(values, scale_hint=None):
    """Symmetric per-page-per-head int8 quantization of a page-shaped
    fp array [..., page_size, heads, head_dim] -> (int8, scale[...,
    heads]).  ``scale_hint`` (same shape as the returned scale) floors
    the scale: pages re-quantized on append keep a monotone scale so
    already-stored tokens never lose range."""
    amax = jnp.max(jnp.abs(values.astype(jnp.float32)), axis=(-3, -1))
    scale = amax / 127.0
    if scale_hint is not None:
        scale = jnp.maximum(scale, scale_hint)
    safe = jnp.maximum(scale, 1e-12)
    q = jnp.clip(
        jnp.round(values.astype(jnp.float32)
                  / safe[..., None, :, None]),
        -127, 127,
    ).astype(jnp.int8)
    return q, jnp.where(scale > 0, scale, 1.0).astype(jnp.float32)


def _paged_update_kv_int8(
    k_slab: QuantizedPages, v_slab: QuantizedPages,
    k_new, v_new, page_table, index, valid_len,
):
    """int8 twin of the fp scatter: quantize AT WRITE TIME.

    Writes land page-at-a-time: for each page a row's new tokens touch,
    the old page is gathered, dequantized, merged with the new
    positions, garbage (``>= valid_len``) zeroed, and re-quantized with
    a per-page-per-head amax scale FLOORED at the page's previous scale
    (``quantize_pages`` hint) — so a page's scale is monotone over its
    tenancy and an append can only widen, never clip, what earlier
    tokens stored.  A page whose first live position is this write
    (``page_start >= index``) takes a fresh scale: whatever the
    previous tenant left in the scale slab is garbage, exactly like the
    value slab's no-zeroing story.

    Shared pages are never written (the pool's COW contract), so the
    per-row page updates are disjoint and scatter order cannot matter —
    the same argument as the fp path, at page granularity.
    """
    num_pages, page_size = k_slab.values.shape[0], k_slab.values.shape[1]
    R, Lq, H, D = k_new.shape
    max_pages = page_table.shape[1]
    index = jnp.reshape(index, (-1,))
    valid = jnp.reshape(valid_len, (-1,))
    # pages a row's span [index, index+Lq) can straddle (static bound)
    n_touch = (Lq - 1) // page_size + 2

    def update_one(slab: QuantizedPages, new) -> QuantizedPages:
        vals, scales = slab.values, slab.scale
        new = new.astype(jnp.float32)
        for j in range(n_touch):
            lp = index // page_size + j  # [R] logical page
            in_span = (lp <= (index + Lq - 1) // page_size) & (
                lp * page_size < valid
            ) & (lp < max_pages)
            phys = jnp.take_along_axis(
                page_table, jnp.clip(lp, 0, max_pages - 1)[:, None],
                axis=1,
            )[:, 0]
            real = in_span & (phys >= 0) & (phys < num_pages)
            src = jnp.clip(phys, 0, num_pages - 1)
            # gathered pages (R of them, never the pool) take their
            # [ps, H, D] shape for the per-(page, head) scale
            old_q = vals[src].reshape(R, page_size, H, D)
            old_s = scales[src]               # [R, H]
            old_f = old_q.astype(jnp.float32) * old_s[:, None, :, None]
            gpos = lp[:, None] * page_size + jnp.arange(
                page_size, dtype=jnp.int32
            )  # [R, ps] global positions of this page
            offset = gpos - index[:, None]
            write_here = (
                (offset >= 0) & (offset < Lq)
                & (gpos < valid[:, None])
            )
            picked = jnp.take_along_axis(
                new,
                jnp.broadcast_to(
                    jnp.clip(offset, 0, Lq - 1)[:, :, None, None],
                    (R, page_size) + new.shape[2:],
                ),
                axis=1,
            )
            merged = jnp.where(write_here[..., None, None], picked,
                               old_f)
            live = gpos < valid[:, None]
            merged = jnp.where(live[..., None, None], merged, 0.0)
            # a page whose live data starts at this write takes a fresh
            # scale (the previous tenant's slab entry is stale garbage)
            has_old = (lp * page_size < index)[:, None]
            hint = jnp.where(has_old, old_s, 0.0)
            q, s = quantize_pages(merged, scale_hint=hint)
            dest = jnp.where(real, phys, num_pages)
            vals = vals.at[dest].set(
                q.reshape(R, page_size, H * D), mode="drop"
            )
            scales = scales.at[dest].set(s, mode="drop")
        return QuantizedPages(vals, scales)

    return update_one(k_slab, k_new), update_one(v_slab, v_new)


def paged_update_kv(
    k_slab, v_slab, k_new, v_new, page_table, index, valid_len
):
    """Scatter ``k_new``/``v_new`` into paged slabs through page tables.

    ``k_slab``/``v_slab``: [num_pages, page_size, heads * head_dim]
    physical page pools; ``k_new``/``v_new``: [R, Lq, heads, head_dim];
    ``page_table``: [R, max_pages] int32, logical page -> physical page,
    padded with an out-of-range sentinel (>= num_pages);
    ``index``: [R] start position of each row's new tokens;
    ``valid_len``: [R] true end position — writes at or beyond it (the
    pad tail of a bucketed prefill) are DROPPED, so pad positions never
    touch a page and a row never writes outside the pages it holds.
    Returns the updated ``(k_slab, v_slab)``.

    Rows never write a page mapped by another holder: the pool's grant
    contract (serving/paging.py) keeps shared pages read-only — a
    partial shared page is copied-on-write into a private page before
    the owner's first append — so scatter destinations are disjoint
    across rows by construction and scatter order cannot matter.

    The scatter goes through the slab's ``[num_pages * page_size, heads
    * head_dim]`` view.  Merging the two LEADING axes leaves the tiled
    minor pair alone, so on a TPU the view is a bitcast wherever
    ``page_size`` is a whole number of sublane tiles (16 rows of
    bfloat16, 8 of float32), and a donated slab is written in place.
    Only ``k_new``/``v_new`` (activations) are reshaped; a slab never
    is, which is why the pool is stored with its heads merged.

    ``k_slab``/``v_slab`` may be :class:`QuantizedPages` (the
    ``kv_dtype="int8"`` pool): writes then quantize at write time with
    per-page-per-head scales kept in the parallel scale slab — see
    :func:`_paged_update_kv_int8`.
    """
    if isinstance(k_slab, QuantizedPages):
        return _paged_update_kv_int8(
            k_slab, v_slab, k_new, v_new, page_table, index, valid_len
        )
    num_pages, page_size = k_slab.shape[0], k_slab.shape[1]
    R, Lq = k_new.shape[0], k_new.shape[1]
    max_pages = page_table.shape[1]
    pos = jnp.reshape(index, (-1, 1)) + jnp.arange(Lq, dtype=jnp.int32)
    logical = pos // page_size
    phys = jnp.take_along_axis(
        page_table, jnp.clip(logical, 0, max_pages - 1), axis=1
    )
    flat = phys * page_size + pos % page_size
    oob = num_pages * page_size  # 'drop' sentinel destination
    keep = (
        (pos < jnp.reshape(valid_len, (-1, 1)))
        & (logical < max_pages)
        & (phys >= 0) & (phys < num_pages)
    )
    flat = jnp.where(keep, flat, oob).reshape(-1)

    def scatter(slab, new):
        flat_slab = slab.reshape(num_pages * page_size, slab.shape[2])
        flat_slab = flat_slab.at[flat].set(
            new.astype(slab.dtype).reshape(R * Lq, -1), mode="drop"
        )
        return flat_slab.reshape(slab.shape)

    return scatter(k_slab, k_new), scatter(v_slab, v_new)


def gather_kv_pages(k_slab, v_slab, page_table, num_heads: int):
    """Per-row virtual cache views through page tables.

    Returns ``(k, v)`` of shape [R, max_pages * page_size, heads,
    head_dim]: row r's logically-contiguous sequence, assembled by
    gathering its pages.  The slabs store heads and head_dim merged;
    the GATHERED view (``R x max_pages`` pages, never the pool) is
    what takes the ``[.., num_heads, head_dim]`` shape.  Sentinel
    table entries clamp into the slab and read garbage — those virtual
    positions are at or beyond the row's current length by the pool's
    covering invariant, so
    :func:`decode_visibility` masks them exactly as it masks a freed
    row's stale tail.

    :class:`QuantizedPages` slabs dequantize during the gather (int8
    value x its page's per-head scale), returning float32 views — the
    XLA reference path's dequant site; the fused kernel
    (``ops/paged_attention.py``) dequantizes per block in VMEM instead
    and never materializes these views at all.
    """
    quantized = isinstance(k_slab, QuantizedPages)
    vals = k_slab.values if quantized else k_slab
    num_pages, page_size = vals.shape[0], vals.shape[1]
    R = page_table.shape[0]
    pos = (
        page_table[:, :, None] * page_size
        + jnp.arange(page_size, dtype=jnp.int32)[None, None, :]
    )
    pos = jnp.clip(pos.reshape(R, -1), 0, num_pages * page_size - 1)

    def rows(values):
        flat = values.reshape(num_pages * page_size, values.shape[2])
        return flat[pos].reshape(R, pos.shape[1], num_heads, -1)

    def gather(slab):
        if isinstance(slab, QuantizedPages):
            page_of = pos // page_size
            return (
                rows(slab.values).astype(jnp.float32)
                * slab.scale[page_of][:, :, :, None]
            )
        return rows(slab)

    return gather(k_slab), gather(v_slab)


# --------------------------------------------------------------------------
# slab specification + allocation
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class KVCacheSpec:
    """Shape/dtype of one attention layer's slab (minus the slot axis)."""

    max_len: int
    num_heads: int
    head_dim: int
    dtype: str = "float32"

    def slab_shape(self, slots: int) -> Tuple[int, int, int, int]:
        return (slots, self.max_len, self.num_heads, self.head_dim)

    def slab_mb(self, slots: int) -> float:
        """Size of the (k, v) slab PAIR in MB."""
        n = float(slots * self.max_len * self.num_heads * self.head_dim)
        return 2.0 * n * jnp.dtype(self.dtype).itemsize / 1024.0**2


def kv_spec_from_config(config, max_len: int) -> KVCacheSpec:
    """Spec from a GPT-style config (dict or object with the fields)."""
    get = (
        config.get if isinstance(config, dict)
        else lambda k, d=None: getattr(config, k, d)
    )
    heads = int(get("num_attention_heads"))
    hidden = int(get("hidden_size"))
    return KVCacheSpec(
        max_len=int(max_len),
        num_heads=heads,
        head_dim=hidden // heads,
        dtype=str(get("dtype", "float32")),
    )


def init_layer_caches(
    specs: Sequence[KVCacheSpec], slots: int, device=None
) -> List[Tuple[jax.Array, jax.Array]]:
    """Zeroed (k, v) slab pairs, one per attention layer, optionally
    committed to ``device``: the single-request decoder's store."""
    caches = []
    for spec in specs:
        shape = spec.slab_shape(slots)
        dtype = jnp.dtype(spec.dtype)
        pair = (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
        if device is not None:
            pair = jax.device_put(pair, device)
        caches.append(pair)
    return caches


def init_paged_caches(
    specs: Sequence[KVCacheSpec],
    num_pages: int,
    page_size: int,
    device=None,
    kv_dtype: Optional[str] = None,
) -> List[Tuple[jax.Array, jax.Array]]:
    """Zeroed paged (k, v) slab pairs ``[num_pages, page_size, heads *
    head_dim]``, one per attention layer: the shape the paged-attention
    kernel copies pages in and the scatter writes rows in, so no
    program ever relays a slab out (on a TPU ``[.., heads, head_dim]``
    and ``[.., heads * head_dim]`` tile the same bytes differently, and
    a reshape between them copies the whole slab).

    ``kv_dtype="int8"`` allocates :class:`QuantizedPages` pairs instead:
    int8 value slabs plus float32 ``[num_pages, heads]`` scale slabs
    (zero scale dequantizes to zero, so no zeroing pass is ever owed) —
    ~4x the pages per MB of a float32 pool, ~2x a bf16 one.
    """
    caches = []
    for spec in specs:
        shape = (num_pages, page_size, spec.num_heads * spec.head_dim)
        if kv_dtype == "int8":
            def one():
                return QuantizedPages(
                    jnp.zeros(shape, jnp.int8),
                    jnp.zeros((num_pages, spec.num_heads),
                              jnp.float32),
                )

            pair = (one(), one())
        elif kv_dtype is not None:
            raise ValueError(
                f"kv_dtype must be 'int8' or None (the model dtype), "
                f"got {kv_dtype!r}"
            )
        else:
            dtype = jnp.dtype(spec.dtype)
            pair = (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
        if device is not None:
            pair = jax.device_put(pair, device)
        caches.append(pair)
    return caches


def paged_kv_mb_per_layer(
    model_cfg: Sequence[dict],
    num_pages: int,
    page_size: int,
    attn_layer_type: str = "GptBlock_Attn",
    kv_dtype: Optional[str] = None,
) -> List[float]:
    """Per-layer paged-pool MB for a layer-config list — the paged twin
    of :func:`kv_mb_per_layer`.  ``kv_dtype=None`` keeps the model
    dtype through the permissive ``jnp.dtype`` itemsize (byte-identical
    to :func:`kv_mb_per_layer` at equal positions — any jnp-valid model
    dtype stays accountable); an
    EXPLICIT ``kv_dtype`` charges through
    ``serving/paging.paged_pool_mb`` — the ONE quantized-width formula
    the allocator, the profiler, and the pre-flight verifier all share
    (so they can never disagree on pool size), strict about its dtype
    table because a silently mis-sized quantized pool is the drift the
    sharing exists to prevent."""
    from .paging import paged_pool_mb

    out: List[float] = []
    for cfg in model_cfg:
        if cfg.get("layer_type") == attn_layer_type:
            spec = kv_spec_from_config(cfg.get("config", {}), page_size)
            if kv_dtype is None:
                out.append(spec.slab_mb(num_pages))
            else:
                out.append(paged_pool_mb(
                    num_pages, page_size, spec.num_heads,
                    spec.head_dim, kv_dtype=kv_dtype,
                ))
        else:
            out.append(0.0)
    return out


def kv_mb_per_layer(
    model_cfg: Sequence[dict],
    slots: int,
    max_len: int,
    attn_layer_type: str = "GptBlock_Attn",
) -> List[float]:
    """Per-layer preallocated KV-slab MB for a layer-config list.

    Non-attention layers contribute 0.0; attention layers contribute
    their (k, v) slab pair at ``slots`` x ``max_len``.  This is the
    memory profile the serving-balanced allocator and the pre-flight
    plan verifier add on top of the parameter/activation formula.
    """
    out: List[float] = []
    for cfg in model_cfg:
        if cfg.get("layer_type") == attn_layer_type:
            spec = kv_spec_from_config(cfg.get("config", {}), max_len)
            out.append(spec.slab_mb(slots))
        else:
            out.append(0.0)
    return out


__all__ = [
    "KVCacheSpec",
    "QuantizedPages",
    "decode_positions",
    "decode_visibility",
    "gather_kv_pages",
    "init_layer_caches",
    "init_paged_caches",
    "kv_mb_per_layer",
    "kv_spec_from_config",
    "paged_kv_mb_per_layer",
    "paged_update_kv",
    "quantize_pages",
    "update_kv_cache",
]
