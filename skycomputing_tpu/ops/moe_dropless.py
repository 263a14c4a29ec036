"""Dropless expert routing over the experts a chip holds.

``ops/moe.py`` dispatches into fixed-capacity buffers and DROPS what does
not fit; this is the other trade.  An expert layer is told which experts
it holds (a range of the published count), routes every token over ALL of
them at the published router width, and computes, for every (token,
chosen expert) pair whose expert lives here, that expert's part of the
result, whatever the imbalance.  Pairs whose expert lives elsewhere are
left out: on one chip the layer runs without its exchange, and nothing
stands in for the absent chips.

Static shapes without dropping: the ``T x k`` pairs are sorted by local
expert (absent experts last), the tokens' rows are gathered in that order
into a ``[T x k, d]`` buffer (the worst case: every choice of every token
held here), and a GROUPED matrix product walks only the row tiles that
belong to a held expert (``group_sizes``).  On a TPU that product is the
Pallas grouped-matmul kernel that ships with JAX (``megablox.gmm``, with
its transposed twin ``tgmm`` for the weights' gradient); elsewhere it is
``lax.ragged_dot``.  Both take the same arguments and give the same rows.

Scopes: the caller wraps routing in ``jax.named_scope("moe_route")`` and
the grouped products in ``"moe_experts"``.
"""

from __future__ import annotations

import functools
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


# -- rows in and out of expert order ----------------------------------------
# ``order`` is a permutation of the T x k (token, choice) pairs and ``inv``
# its inverse, so both directions are GATHERS, forward and backward: the
# transpose of a gather by a permutation is the gather by its inverse, which
# autodiff would write as a scatter-add.

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rows_in_expert_order(tokens, order, inv, k: int):
    return tokens[order // k]


def _rows_fwd(tokens, order, inv, k):
    return tokens[order // k], (order, inv, tokens.shape[0])


def _rows_bwd(k, res, g):
    order, inv, T = res
    return g[inv].reshape(T, k, g.shape[-1]).sum(axis=1), None, None


_rows_in_expert_order.defvjp(_rows_fwd, _rows_bwd)


@jax.custom_vjp
def _rows_in_token_order(rows, order, inv):
    return rows[inv]


def _back_fwd(rows, order, inv):
    return rows[inv], (order,)


def _back_bwd(res, g):
    (order,) = res
    return g[order], None, None


_rows_in_token_order.defvjp(_back_fwd, _back_bwd)


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


def dropless_experts(tokens, idx, weights, w_up, w_down, *,
                     held_start: int, impl: Optional[str] = None):
    """The held experts' part of a routed layer, nothing dropped.

    tokens  [T, d]       (compute dtype)
    idx     [T, k] int32 chosen experts out of the published count
    weights [T, k] f32   their combine weights
    w_up    [E, d, f]    the E experts held here: expert ``held_start + i``
    w_down  [E, f, d]    is row ``i``; an expert is ``w_down relu(w_up x)^2``

    Returns ``(y [T, d] float32, counts [E + 2] int32)``: the weighted sum
    over the token's chosen experts that are held here, and the counters
    ``tokens to each held expert ..., pairs routed here, pairs dropped``.
    The last is counted from what the grouped products WROTE: the pairs
    routed here less the rows of their result that are not all zero in
    their first ``_WRITTEN_COLUMNS`` columns (a row no group owns, or one a
    product skipped, comes out zero; an expert's output for a real token
    does not).  It must read 0.
    """
    T, k = idx.shape
    E = w_up.shape[0]
    cd = tokens.dtype
    local = idx - held_start
    held = (local >= 0) & (local < E)
    gid = jnp.where(held, local, E).reshape(-1)          # absent experts last
    order = jnp.argsort(gid, stable=True).astype(jnp.int32)
    inv = jnp.zeros_like(order).at[order].set(
        jnp.arange(T * k, dtype=jnp.int32))
    sizes = jnp.bincount(gid, length=E + 1).astype(jnp.int32)

    rows = _rows_in_expert_order(tokens, order, inv, k)  # [T*k, d]
    hidden = grouped_matmul(rows, w_up.astype(cd), sizes, impl=impl)
    hidden = jnp.square(jax.nn.relu(hidden))             # relu2, no gate
    out = grouped_matmul(hidden, w_down.astype(cd), sizes, impl=impl)
    out = _rows_in_token_order(out, order, inv).reshape(T, k, -1)
    combine = jnp.where(held, weights, 0.0)
    y = jnp.einsum("tkd,tk->td", out.astype(jnp.float32), combine)

    routed_here = held.sum().astype(jnp.int32)
    written = jnp.any(out[..., :_WRITTEN_COLUMNS] != 0, axis=-1)
    written = (written & held).sum().astype(jnp.int32)
    counts = jnp.concatenate([
        sizes[:E], routed_here[None], (routed_here - written)[None]])
    return y, counts


__all__ = ["route_top_k", "grouped_matmul", "dropless_experts",
           "traced_for_tpu"]
