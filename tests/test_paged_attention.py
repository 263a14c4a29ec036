"""Fused paged-attention kernel contracts (CPU-deterministic, tier-1).

The kernel (``ops/paged_attention.py``) walks the page table inside a
Pallas program; off-TPU it runs in interpret mode, which is how this
suite pins it — bit-level agreement with the XLA reference on the
contract's edge cases (page-boundary crossings, sentinel-padded tables,
1-row and full-wave shapes, decode and speculative-verify query
lengths), and bounded error for the int8-quantized page variant whose
dequant happens in-kernel.  The engine-level routing (``attn_impl=``,
``kv_dtype=``, the bounded live gather) is pinned in
``tests/test_serving.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skycomputing_tpu.ops.paged_attention import (
    paged_attention,
    paged_attention_reference,
)
from skycomputing_tpu.serving.kv_cache import (
    QuantizedPages,
    gather_kv_pages,
    init_paged_caches,
    paged_update_kv,
    quantize_pages,
)
from skycomputing_tpu.serving import KVCacheSpec

pytestmark = pytest.mark.serving

P, PS, H, D = 10, 4, 2, 16


def _case(rng, R, Lq, tables, index, quantized=False):
    q = rng.standard_normal((R, Lq, H, D)).astype(np.float32)
    if quantized:
        k = rng.integers(-127, 128, (P, PS, H * D)).astype(np.int8)
        v = rng.integers(-127, 128, (P, PS, H * D)).astype(np.int8)
        ks = rng.uniform(0.005, 0.03, (P, H)).astype(np.float32)
        vs = rng.uniform(0.005, 0.03, (P, H)).astype(np.float32)
        out = paged_attention(q, k, v, tables, index, k_scale=ks,
                              v_scale=vs, interpret=True)
        ref = paged_attention_reference(q, k, v, tables, index,
                                        k_scale=ks, v_scale=vs)
    else:
        k = rng.standard_normal((P, PS, H * D)).astype(np.float32)
        v = rng.standard_normal((P, PS, H * D)).astype(np.float32)
        out = paged_attention(q, k, v, tables, index, interpret=True)
        ref = paged_attention_reference(q, k, v, tables, index)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_kernel_matches_reference_across_page_boundary():
    """A sequence whose causal bound sits mid-table (crossing page
    boundaries) produces the reference output exactly — the online
    softmax accumulates the same masked blocks the gather would."""
    rng = np.random.default_rng(0)
    t = np.full((1, 3), P, np.int32)
    t[0, :3] = [7, 2, 5]
    _case(rng, 1, 1, t, np.array([8], np.int32))  # len 9 over ps=4


def test_kernel_masks_sentinel_and_clamped_entries():
    """Sentinel table entries (>= num_pages) clamp to a real page whose
    positions are past the causal bound — masked garbage, never a NaN
    (the fully-masked-block skip) and never a wrong value."""
    rng = np.random.default_rng(1)
    t = np.full((3, 5), P, np.int32)
    t[0, :3] = [7, 2, 5]
    t[1, :2] = [0, 9]
    t[2, :5] = [1, 3, 4, 6, 8]
    _case(rng, 3, 1, t, np.array([8, 4, 16], np.int32))
    out_sentinel_heavy = np.full((2, 4), P, np.int32)
    out_sentinel_heavy[0, 0] = 3
    out_sentinel_heavy[1, 0] = 1
    _case(rng, 2, 1, out_sentinel_heavy, np.array([0, 2], np.int32))


def test_kernel_verify_shape_and_full_wave():
    """The speculative-verify query length (Lq = k + 1) and a full wave
    of rows agree with the reference — one program shape per (rows,
    Lq, width), the engine's compile discipline."""
    rng = np.random.default_rng(2)
    t = np.full((3, 5), P, np.int32)
    t[0, :3] = [7, 2, 5]
    t[1, :2] = [0, 9]
    t[2, :5] = [1, 3, 4, 6, 8]
    _case(rng, 3, 4, t, np.array([5, 0, 12], np.int32))


def test_kernel_int8_dequant_matches_reference():
    """The in-kernel dequant (int8 block x per-page-per-head scale)
    equals the materializing dequantized gather."""
    rng = np.random.default_rng(3)
    t = np.full((3, 5), P, np.int32)
    t[0, :3] = [7, 2, 5]
    t[1, :2] = [0, 9]
    t[2, :5] = [1, 3, 4, 6, 8]
    _case(rng, 3, 1, t, np.array([8, 4, 16], np.int32),
          quantized=True)


# --------------------------------------------------------------------------
# the blocking: live pages only, several to a step, heads stacked by shape
# --------------------------------------------------------------------------


def _blocked_case(seed, heads, head_dim, query_len, lengths, width,
                  quantized):
    """Rows whose LAST query sits at ``length - 1``, over pages of 4
    drawn without repeats, the table's tail left at the sentinel."""
    rng = np.random.default_rng(seed)
    rows = len(lengths)
    pages = sum(-(-n // PS) for n in lengths) + 3
    table = np.full((rows, width), pages, np.int32)
    free = rng.permutation(pages)
    for r, n in enumerate(lengths):
        held, free = free[: -(-n // PS)], free[-(-n // PS):]
        table[r, : held.size] = held
    index = np.asarray(lengths, np.int32) - query_len
    q = rng.standard_normal(
        (rows, query_len, heads, head_dim)).astype(np.float32)
    shape = (pages, PS, heads * head_dim)
    if quantized:
        k = rng.integers(-127, 128, shape).astype(np.int8)
        v = rng.integers(-127, 128, shape).astype(np.int8)
        scales = dict(
            k_scale=rng.uniform(0.002, 0.01, (pages, heads)).astype(
                np.float32),
            v_scale=rng.uniform(0.005, 0.03, (pages, heads)).astype(
                np.float32),
        )
    else:
        k = rng.standard_normal(shape).astype(np.float32)
        v = rng.standard_normal(shape).astype(np.float32)
        scales = {}
    out = paged_attention(q, k, v, table, index, interpret=True, **scales)
    ref = paged_attention_reference(q, k, v, table, index, **scales)
    assert np.all(np.isfinite(np.asarray(out)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


# a 24-column table of 4-token pages is walked 16 pages (64 positions)
# to a step: a row of one page, one ending on the step's last position,
# one a position past it, one filling the table; and a table far wider
# than any of its rows, the second step never taken
BLOCKED_ROWS = {
    "mixed": [4, 64, 65, 96],
    "short": [4, 9, 30],
}
# H*D of 32 and 192 are whole-row blocks (no lane-aligned head group);
# 256 is two lane tiles, its heads stacked four or two at a time
BLOCKED_HEADS = {"row32": (2, 16), "row192": (3, 64), "row256": (4, 64)}


def test_blocking_of_the_test_shapes_is_what_the_cases_assume():
    from skycomputing_tpu.ops.paged_attention import (
        _heads_per_block,
        _pages_per_step,
        _query_rows_per_step,
    )

    assert _pages_per_step(PS, 32 * 4, 24) == 16
    assert _pages_per_step(PS, 256, 80) == 64
    assert _query_rows_per_step(4, 4, 64, 4) == 4
    assert _query_rows_per_step(300, 4, 64, 4) == 256
    assert _heads_per_block(4, 64, 1) == 4
    assert _heads_per_block(4, 64, 256) == 2
    assert _heads_per_block(3, 64, 1) == 3 == _heads_per_block(3, 64, 256)
    # the benchmark's cell: 8 bf16 pages of 40 KB a step, the whole row
    # of heads to a decode tick's matmul, a head pair to a prefill block
    assert _pages_per_step(16, 1280 * 2, 64) == 8
    assert _query_rows_per_step(768, 20, 64, 2) == 128
    assert _heads_per_block(20, 64, 1) == 20
    assert _heads_per_block(20, 64, 128) == 2


@pytest.mark.parametrize("kv", ["fp", "int8"])
@pytest.mark.parametrize("heads", list(BLOCKED_HEADS))
@pytest.mark.parametrize("rows", list(BLOCKED_ROWS))
@pytest.mark.parametrize("query_len", [1, 4])
def test_kernel_walks_live_pages_of_unequal_rows(query_len, rows, heads,
                                                 kv):
    """Rows of unequal length under one table: each row's walk ends at
    its own last live page (the table's sentinel tail is never read),
    whether that page closes a step, opens the next or is the table's
    last column, for decode and verify query lengths."""
    H_, D_ = BLOCKED_HEADS[heads]
    _blocked_case(11, H_, D_, query_len, BLOCKED_ROWS[rows], 24,
                  kv == "int8")


@pytest.mark.parametrize("kv", ["fp", "int8"])
@pytest.mark.parametrize("heads", ["row32", "row256"])
def test_kernel_tiles_long_query_blocks(heads, kv):
    """A prefill-length query block is walked 256 rows at a time (the
    second block partial), each block to its own causal bound, with a
    cached prefix before the first query of one row."""
    H_, D_ = BLOCKED_HEADS[heads]
    _blocked_case(12, H_, D_, 300, [300, 320], 80, kv == "int8")


# --------------------------------------------------------------------------
# int8 write-time quantization (the scale slab's contract)
# --------------------------------------------------------------------------

# jitted as the engine runs them (one compile per shape, shared by the
# tests below) — eager op-by-op dispatch made these the file's slowest
_update_kv = jax.jit(paged_update_kv)
_gather_kv = jax.jit(lambda k, v, table: gather_kv_pages(k, v, table, H))


def test_int8_update_bounded_error_and_midpage_valid():
    """Quantize-on-write round-trips within int8 error bounds, a
    mid-page ``valid_len`` zeroes the garbage tail (it must not poison
    the page's amax scale), and positions past ``valid_len`` never
    influence stored values."""
    spec = KVCacheSpec(max_len=32, num_heads=H, head_dim=D,
                       dtype="float32")
    (kq, vq), = init_paged_caches([spec], P, PS, kv_dtype="int8")
    (kf, vf), = init_paged_caches([spec], P, PS)
    rng = np.random.default_rng(4)
    table = np.full((2, 4), P, np.int32)
    table[0, :3] = [3, 1, 5]
    table[1, :2] = [0, 2]
    R, Lq = 2, 9
    knew = rng.standard_normal((R, Lq, H, D)).astype(np.float32)
    vnew = rng.standard_normal((R, Lq, H, D)).astype(np.float32)
    # row 1 ends MID-PAGE: valid 5 of a 9-token write — the pad tail
    # (offsets 5..8) must drop, and page garbage past 5 must read 0
    index = np.array([0, 0], np.int32)
    valid = np.array([9, 5], np.int32)
    args = (jnp.asarray(table), jnp.asarray(index), jnp.asarray(valid))
    kq2, vq2 = _update_kv(kq, vq, jnp.asarray(knew),
                               jnp.asarray(vnew), *args)
    kf2, vf2 = _update_kv(kf, vf, jnp.asarray(knew),
                               jnp.asarray(vnew), *args)
    gq, _ = _gather_kv(kq2, vq2, jnp.asarray(table))
    gf, _ = _gather_kv(kf2, vf2, jnp.asarray(table))
    for r in range(R):
        n = int(valid[r])
        ref = np.asarray(gf)[r, :n]
        err = np.max(np.abs(np.asarray(gq)[r, :n] - ref))
        assert err / np.max(np.abs(ref)) < 0.02, (
            "int8 write round-trip exceeded the error bound"
        )
    # the mid-page garbage tail of row 1's second page reads exactly 0
    # (zeroed at quantization so stale values can't poison the scale)
    tail = np.asarray(gq)[1, 5:8]
    np.testing.assert_array_equal(tail, np.zeros_like(tail))


def test_int8_append_keeps_scale_monotone():
    """A decode append re-quantizes its tail page with a scale floored
    at the page's previous scale — earlier tokens never lose range, so
    repeated appends stay within the same bounded error."""
    spec = KVCacheSpec(max_len=32, num_heads=H, head_dim=D,
                       dtype="float32")
    (kq, vq), = init_paged_caches([spec], P, PS, kv_dtype="int8")
    rng = np.random.default_rng(5)
    table = np.full((1, 2), P, np.int32)
    table[0, :2] = [4, 6]
    # big first token, then small appends: amax would SHRINK without
    # the monotone floor and re-quantize the first token coarsely
    big = 8.0 * rng.standard_normal((1, 1, H, D)).astype(np.float32)
    kq, vq = _update_kv(
        kq, vq, jnp.asarray(big), jnp.asarray(big),
        jnp.asarray(table), jnp.asarray([0]), jnp.asarray([1]),
    )
    scale_after_big = np.asarray(kq.scale[4]).copy()
    small = 0.01 * rng.standard_normal((1, 1, H, D)).astype(np.float32)
    for step in range(1, 4):
        kq, vq = _update_kv(
            kq, vq, jnp.asarray(small), jnp.asarray(small),
            jnp.asarray(table), jnp.asarray([step]),
            jnp.asarray([step + 1]),
        )
    assert np.all(np.asarray(kq.scale[4]) >= scale_after_big - 1e-9)
    gk, _ = _gather_kv(kq, vq, jnp.asarray(table))
    rel = np.max(np.abs(np.asarray(gk)[0, 0] - big[0, 0])) / np.max(
        np.abs(big)
    )
    assert rel < 0.02


def test_quantize_pages_fresh_page_ignores_stale_scale():
    """quantize_pages with a zero hint (a fresh page) picks the amax
    scale; with a larger hint it floors to the hint — the two rules
    behind stale-slab safety and append monotonicity."""
    rng = np.random.default_rng(6)
    page = rng.standard_normal((1, PS, H, D)).astype(np.float32)
    q, s = quantize_pages(jnp.asarray(page))
    amax = np.abs(page).max(axis=(1, 3))
    np.testing.assert_allclose(np.asarray(s), amax / 127.0, rtol=1e-6)
    q2, s2 = quantize_pages(
        jnp.asarray(page), scale_hint=jnp.full((1, H), 1e3)
    )
    np.testing.assert_allclose(np.asarray(s2), 1e3)
    # an all-zero page quantizes to zeros with the safe unit scale
    qz, sz = quantize_pages(jnp.zeros((1, PS, H, D)))
    assert np.all(np.asarray(qz) == 0) and np.all(np.asarray(sz) == 1.0)


def test_quantized_pages_ride_jit_and_pytrees():
    """QuantizedPages is a pytree: it crosses jit boundaries (the
    engine's donated stage programs) with type and dtypes intact."""
    qp = QuantizedPages(jnp.zeros((P, PS, H * D), jnp.int8),
                        jnp.ones((P, H), jnp.float32))

    @jax.jit
    def bump(s):
        return QuantizedPages(s.values, s.scale * 2.0)

    out = bump(qp)
    assert isinstance(out, QuantizedPages)
    assert out.values.dtype == jnp.int8
    assert float(out.scale[0, 0]) == 2.0


# --------------------------------------------------------------------------
# the pool's stored shape: [num_pages, page_size, H * D] holds, byte for
# byte, what a [num_pages, page_size, H, D] pool held
# --------------------------------------------------------------------------


def _write_4d(k_new, table, index, valid, quantized):
    """What a ``[P, PS, H, D]`` pool holds after one write into zeroed
    pages: a plain loop over rows and positions, and for int8 over the
    pages a row touched (garbage past ``valid`` zeroed, one
    ``quantize_pages`` scale per page and head).  Returns (values,
    scale or None)."""
    values = np.zeros((P, PS, H, D), np.float32)
    touched = set()
    for r in range(k_new.shape[0]):
        for j in range(k_new.shape[1]):
            pos = int(index[r]) + j
            if pos >= int(valid[r]) or pos // PS >= table.shape[1]:
                continue  # the pad tail is dropped
            page = int(table[r, pos // PS])
            if page >= P:
                continue
            values[page, pos % PS] = k_new[r, j]
            touched.add(page)
    if not quantized:
        return values, None
    q = np.zeros((P, PS, H, D), np.int8)
    scale = np.zeros((P, H), np.float32)
    pages = sorted(touched)
    # ``quantize_pages`` speaks [.., PS, H, D] pages, as it always did
    q[pages], scale[pages] = jax.jit(quantize_pages)(values[pages])
    return q, scale


@pytest.mark.parametrize("kv", ["fp", "int8"])
def test_merged_heads_pool_equals_the_4d_pool_bit_for_bit(kv):
    """``paged_update_kv`` then ``gather_kv_pages`` on the stored shape
    give the bytes a ``[P, PS, H, D]`` pool gave: a write that crosses
    a page boundary mid-page, a second row whose pad tail (positions at
    or past ``valid_len``) is dropped, unwritten pages left as they
    were."""
    quantized = kv == "int8"
    spec = KVCacheSpec(max_len=32, num_heads=H, head_dim=D,
                       dtype="float32")
    (k0, v0), = init_paged_caches(
        [spec], P, PS, kv_dtype="int8" if quantized else None
    )
    assert (k0.values if quantized else k0).shape == (P, PS, H * D)
    rng = np.random.default_rng(8)
    table = np.full((2, 4), P, np.int32)
    table[0, :3] = [3, 1, 5]
    table[1, :2] = [0, 2]
    index = np.array([2, 0], np.int32)   # row 0: positions 2..8, 3 pages
    valid = np.array([9, 5], np.int32)   # row 1: 5 of 7 written
    k_new = rng.standard_normal((2, 7, H, D)).astype(np.float32)
    v_new = rng.standard_normal((2, 7, H, D)).astype(np.float32)
    k1, v1 = _update_kv(
        k0, v0, jnp.asarray(k_new), jnp.asarray(v_new),
        jnp.asarray(table), jnp.asarray(index), jnp.asarray(valid),
    )
    wants = [_write_4d(new, table, index, valid, quantized)
             for new in (k_new, v_new)]
    for slab, (want, want_scale) in zip((k1, v1), wants):
        got = np.asarray(slab.values if quantized else slab)
        assert got.shape == (P, PS, H * D)
        assert got.tobytes() == want.astype(got.dtype).tobytes()
        if quantized:  # untouched pages keep the fresh pool's zero scale
            np.testing.assert_array_equal(
                np.asarray(slab.scale), want_scale
            )
    # the gathered view is what takes the [.., H, D] shape
    gathered = _gather_kv(k1, v1, jnp.asarray(table))
    for got, (want, want_scale) in zip(gathered, wants):
        assert got.shape == (2, 4 * PS, H, D)
        if quantized:
            want = want.astype(np.float32) * want_scale[:, None, :, None]
        for r in range(2):
            pages = np.minimum(table[r], P - 1)
            np.testing.assert_array_equal(
                np.asarray(got)[r], want[pages].reshape(4 * PS, H, D)
            )
