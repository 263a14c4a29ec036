"""Dropless expert routing over the experts a chip holds.

``ops/moe.py`` dispatches into fixed-capacity buffers and DROPS what does
not fit; this is the other trade.  An expert layer is told which experts
it holds (a range of the published count), routes every token over ALL of
them at the published router width, and computes, for every (token,
chosen expert) pair whose expert lives here, that expert's part of the
result, whatever the imbalance.  Pairs whose expert lives elsewhere are
left out: on one chip the layer runs without its exchange, and nothing
stands in for the absent chips.

Static shapes without dropping, and work that follows the routed count.
The ``T x k`` pairs are sorted by local expert (held pairs first, absent
experts last).  The expert order is walked in BLOCKS of ``C =
block_rows(...)`` rows (twice the share uniform routing would bring the
held experts, in row tiles of 128), as many as hold a held pair: ``ceil(
routed / C)`` trips of ONE body in a ``lax.fori_loop`` whose bound is that
device value.  A block gathers its ``C`` token rows, runs the GROUPED
matrix products over ``[C, .]`` with its own group sizes, and ADDS each
result row, weighted in float32, into ``y [T, d]`` at its token.  No
array of ``T x k`` rows of width ``d`` or ``f`` exists; the worst case
(every choice of every token held here) is the same body run ``T x k /
C`` times.  On a TPU the product is the Pallas grouped-matmul kernel that
ships with JAX (``megablox.gmm``, with its transposed twin ``tgmm`` for the
weights' gradient); elsewhere it is ``lax.ragged_dot``.  Both take the
same arguments and give the same rows.

Backward: a ``while`` with a traced bound has no reverse mode, so the
expert part is ONE ``jax.custom_vjp`` whose residuals are its inputs and
the small index arrays; its backward is a second loop of the same trip
count that adds each block's pulled-back cotangents into float32 carries.
Where ``C == T x k`` there is no loop, only the block.

Scopes: the caller wraps routing in ``jax.named_scope("moe_route")``; the
expert part opens ``"moe_experts"`` itself, INSIDE the loops' bodies and
around what precedes and follows them, never around a loop: a ``while``
is, in a device trace, an event that spans its body's operations, and
under the scope its time would count twice.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def route_top_k(logits, correction_bias, k: int, *, norm_topk_prob: bool,
                scaling_factor: float) -> Tuple[jax.Array, jax.Array]:
    """Sigmoid top-k routing with a selection-only bias.

    ``logits`` [T, E] float32.  Scores are ``sigmoid(logits)``; the ``k``
    experts are chosen on ``scores + correction_bias`` (the bias steers
    the choice and carries no gradient), the weights are the scores at
    the chosen experts, divided by their sum if ``norm_topk_prob``, times
    ``scaling_factor``.  Returns ``(indices [T, k] int32, weights [T, k]
    float32)``.
    """
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    choice = scores + jax.lax.stop_gradient(
        correction_bias.astype(jnp.float32))
    _, idx = jax.lax.top_k(choice, k)
    weights = jnp.take_along_axis(scores, idx, axis=1)
    if norm_topk_prob:
        weights = weights / (weights.sum(axis=1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), weights * scaling_factor


def _gmm_tiling(m: int, k: int, n: int) -> Tuple[int, int, int]:
    """Row tiles of 128 (a held expert's few hundred rows fill whole
    tiles), contraction and output tiles as wide as divide evenly up to
    1024: fewer grid steps than the kernel's 128-cube default."""
    def widest(x):
        for t in (1024, 896, 768, 640, 512, 384, 256, 128):
            if x % t == 0:
                return t
        return 128

    return (128 if m % 128 == 0 else m, widest(k), widest(n))


def grouped_matmul(lhs, rhs, group_sizes, *, impl: Optional[str] = None):
    """``out[rows of group i] = lhs[rows of group i] @ rhs[i]``.

    ``lhs`` [m, k] rows sorted by group; ``rhs`` [E, k, n]; ``group_sizes``
    [E + 1] int32, the last group being the rows no held expert owns:
    they come out zero.  ``impl``: ``"pallas"`` (TPU), ``"pallas_interpret"``
    (the same kernel interpreted, for a CPU test), ``"xla"``
    (``lax.ragged_dot``), default by where the program runs.
    """
    if impl is None:
        impl = "pallas" if traced_for_tpu() else "xla"
    E = rhs.shape[0]
    if impl == "xla":
        return jax.lax.ragged_dot(
            lhs, rhs, group_sizes[:E],
            preferred_element_type=lhs.dtype,
        )
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m, k = lhs.shape
    return gmm(
        lhs, rhs, group_sizes, lhs.dtype, _gmm_tiling(m, k, rhs.shape[2]),
        jnp.zeros((), jnp.int32),  # the held experts are groups 0 .. E-1
        None, False, impl == "pallas_interpret",
    )


def traced_for_tpu() -> bool:
    """Is the program being traced meant for a TPU?  The default backend,
    unless a ``jax.default_device`` scope says otherwise (the parameter
    server initialises on the host CPU of a TPU process)."""
    dev = jax.config.jax_default_device
    if dev is not None:
        return getattr(dev, "platform", str(dev)) == "tpu"
    return jax.default_backend() == "tpu"


#: columns of a result row looked at to tell a written row from a zero one
_WRITTEN_COLUMNS = 128

#: a block's rows over the held experts' share under uniform routing
_BLOCK_OVER_UNIFORM = 2


def block_rows(num_tokens: int, k: int, held: int, published: int) -> int:
    """``C``: the rows of one block of the expert order.
    ``_BLOCK_OVER_UNIFORM`` times the ``held`` experts' share of the
    ``num_tokens x k`` pairs were they spread evenly over the ``published``
    experts, rounded up to the grouped product's row tile of 128, and never
    more than all pairs (then one block is the whole order)."""
    share = num_tokens * k * held / published
    tiles = math.ceil(_BLOCK_OVER_UNIFORM * share / 128)
    return min(tiles * 128, num_tokens * k)


def _block_rows(rows, combine, w_up, w_down, block_sizes, impl):
    """``(weighted [C, d] float32, out [C, d])``: ``w_down relu(w_up x)^2``
    for a block's gathered rows, sorted by held expert, and the same times
    each row's combine weight."""
    hidden = grouped_matmul(rows, w_up, block_sizes, impl=impl)
    hidden = jnp.square(jax.nn.relu(hidden))             # relu2, no gate
    out = grouped_matmul(hidden, w_down, block_sizes, impl=impl)
    return out.astype(jnp.float32) * combine[:, None], out


def _block_inputs(c, rows: int, tokens, weights, order, offsets):
    """What block ``c`` reads, forward and backward: rows ``[c * rows, (c +
    1) * rows)`` of the expert order.  ``(head [rows], token [rows],
    is_held [rows], block_sizes [E + 1], gathered [rows, d], combine [rows]
    float32)``: the pairs there and their tokens; which of them a held
    expert owns (held pairs come first, so a prefix); the block's own
    group sizes (each held expert's rows clipped to the block, the
    remainder in the last group, which no held expert owns); the tokens'
    rows; and the pairs' combine weights, 0 where not held."""
    start = c * rows
    head = jax.lax.dynamic_slice(order, (start,), (rows,))
    clipped = jnp.clip(offsets - start, 0, rows)
    block_sizes = jnp.concatenate([
        clipped[1:] - clipped[:-1], (rows - clipped[-1])[None]])
    is_held = jnp.arange(rows) < clipped[-1]
    token = head // weights.shape[1]
    combine = jnp.where(is_held, weights.reshape(-1)[head], 0.0)
    return head, token, is_held, block_sizes, tokens[token], combine


def _walk(rows: int, order, offsets, body, carry):
    """``(carry, blocks walked)`` of ``body(c, carry)`` over the blocks
    that hold a held pair: a loop whose trip count is the load; one block
    that is the whole order takes no loop."""
    if rows == order.size:
        return body(0, carry), jnp.ones((), jnp.int32)
    trips = (offsets[-1] + rows - 1) // rows
    return jax.lax.fori_loop(0, trips, body, carry), trips


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _held_experts(rows: int, impl, tokens, weights, w_up, w_down, order,
                  offsets):
    """``(y [T, d] float32, rows written, blocks walked)`` over blocks of
    ``rows`` rows of the expert order; ``offsets [E + 1]`` is where each
    held expert's rows start in it, and where the last one's end."""
    cd = tokens.dtype
    with jax.named_scope("moe_experts"):
        w_up, w_down = w_up.astype(cd), w_down.astype(cd)
        carry = (jnp.zeros((tokens.shape[0], w_down.shape[2]), jnp.float32),
                 jnp.zeros((), jnp.int32))

    def block(c, carry):
        y, written = carry
        with jax.named_scope("moe_experts"):
            _, token, is_held, block_sizes, gathered, combine = \
                _block_inputs(c, rows, tokens, weights, order, offsets)
            weighted, out = _block_rows(gathered, combine, w_up, w_down,
                                        block_sizes, impl)
            wrote = jnp.any(out[:, :_WRITTEN_COLUMNS] != 0, axis=-1)
            return (y.at[token].add(weighted),
                    written + (wrote & is_held).sum(dtype=jnp.int32))

    (y, written), walked = _walk(rows, order, offsets, block, carry)
    return y, written, walked


def _held_fwd(rows, impl, *inputs):
    return _held_experts(rows, impl, *inputs), inputs


def _held_bwd(rows, impl, inputs, cotangents):
    tokens, weights, w_up, w_down, order, offsets = inputs
    cd = tokens.dtype
    with jax.named_scope("moe_experts"):
        g = cotangents[0]
        up, down = w_up.astype(cd), w_down.astype(cd)
        carry = tuple(jnp.zeros(x.shape, jnp.float32) for x in (
            tokens, weights.reshape(-1), w_up, w_down))

    def block(c, carry):
        with jax.named_scope("moe_experts"):
            head, token, is_held, block_sizes, gathered, combine = \
                _block_inputs(c, rows, tokens, weights, order, offsets)
            _, pull = jax.vjp(
                lambda *operands: _block_rows(*operands, block_sizes,
                                              impl)[0],
                gathered, combine, up, down)
            d_rows, d_combine, d_up, d_down = pull(g[token])
            d_tokens, d_weights, sum_up, sum_down = carry
            return (
                # the gather's transpose: rows added at their tokens
                d_tokens.at[token].add(d_rows.astype(jnp.float32)),
                d_weights.at[head].add(jnp.where(is_held, d_combine, 0.0)),
                sum_up + d_up.astype(jnp.float32),
                sum_down + d_down.astype(jnp.float32),
            )

    (d_tokens, d_weights, d_up, d_down), _ = _walk(rows, order, offsets,
                                                   block, carry)
    with jax.named_scope("moe_experts"):
        return (d_tokens.astype(cd), d_weights.reshape(weights.shape),
                d_up.astype(w_up.dtype), d_down.astype(w_down.dtype),
                None, None)


_held_experts.defvjp(_held_fwd, _held_bwd)


def dropless_experts(tokens, idx, weights, w_up, w_down, *,
                     held_start: int, num_experts: int,
                     impl: Optional[str] = None):
    """The held experts' part of a routed layer, nothing dropped.

    tokens  [T, d]       (compute dtype)
    idx     [T, k] int32 chosen experts out of the ``num_experts`` published
    weights [T, k] f32   their combine weights
    w_up    [E, d, f]    the E experts held here: expert ``held_start + i``
    w_down  [E, f, d]    is row ``i``; an expert is ``w_down relu(w_up x)^2``

    Returns ``(y [T, d] float32, counts [E + 2] int32, blocks int32)``:
    the weighted sum over the token's chosen experts that are held here;
    the counters ``tokens to each held expert ..., pairs routed here,
    pairs dropped``; and how many blocks of ``block_rows`` rows the call
    walked (``ceil(pairs routed here / block_rows)``; 1 where one block
    is the whole order).  ``pairs dropped`` is counted from what the
    grouped products WROTE: the pairs routed here less the rows of their
    result that are not all zero in their first ``_WRITTEN_COLUMNS``
    columns (a row no group owns, or one a product skipped, comes out
    zero; an expert's output for a real token does not).  It must read 0.
    """
    T, k = idx.shape
    E = w_up.shape[0]
    with jax.named_scope("moe_experts"):
        local = idx - held_start
        gid = jnp.where((local >= 0) & (local < E), local, E).reshape(-1)
        # held pairs first, absent experts last
        order = jnp.argsort(gid, stable=True).astype(jnp.int32)
        # a comparison with each group, summed (``jnp.bincount`` is a
        # scatter-add of T x k scalars: 0.22 ms a call on the v5e)
        sizes = (gid[:, None] == jnp.arange(E)).sum(0, dtype=jnp.int32)
        offsets = jnp.concatenate([jnp.zeros(1, jnp.int32),
                                   jnp.cumsum(sizes)])
    y, written, blocks = _held_experts(
        block_rows(T, k, E, num_experts), impl, tokens, weights, w_up,
        w_down, order, offsets)
    counts = jnp.concatenate([
        sizes, offsets[-1:], (offsets[-1] - written)[None]])
    return y, counts, blocks


__all__ = ["route_top_k", "grouped_matmul", "dropless_experts",
           "block_rows", "traced_for_tpu"]
