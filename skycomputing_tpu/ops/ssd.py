"""Mamba-2 state-space duality (SSD) scan, chunked.

The recurrence a Mamba-2 layer computes, a head ``h`` with state
``[P, N]`` (head width x state width), a position ``t``:

    state_t = exp(dt_t * A) * state_{t-1} + dt_t * x_t (outer) B_t
    y_t     = state_t . C_t + D * x_t

``ssd_scan`` computes it in chunks of ``chunk`` positions (Dao & Gu 2024,
"Transformers are SSMs", section 6): inside a chunk as masked matrix
products (the ``[L, L]`` decay-weighted ``C B^T`` block times ``x``),
between chunks as a recurrence on the ``[P, N]`` states, one step a
chunk.  The backward pass is JAX's own through these products and the
``lax.scan`` over chunks: the same chunked form, transposed.

Precision: the big intra-chunk products take their operands as they come
(bfloat16 on the training path) and accumulate in float32; every decay,
cumulative sum, chunk state and the recurrence between chunks is float32
(``STATE_DTYPE``), and the two products that build and read the states run
at ``Precision.HIGHEST`` so a float32 state is not rounded through the
MXU's bfloat16 passes.  They are 4% of a layer's FLOPs.

Everything runs under ``jax.named_scope("ssd_scan")``: the device trace
names the scan's operations, forward and backward, by it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: dtype of the chunk states and of the recurrence between chunks
STATE_DTYPE = jnp.float32

_HIGHEST = jax.lax.Precision.HIGHEST


def ssd_scan(x, dt, A, B, C, D=None, *, chunk: int = 128,
             return_final_state: bool = False):
    """Chunked SSD scan.

    x  [b, t, h, p]  inputs a head (any float dtype; sets the output's)
    dt [b, t, h]     step sizes, already positive (after softplus)
    A  [h]           negative decay rates
    B  [b, t, g, n]  input projections, ``g`` groups each serving ``h // g``
    C  [b, t, g, n]  consecutive heads
    D  [h] or None   skip weight

    Returns ``y [b, t, h, p]`` (and the state after the last position,
    ``[b, h, p, n]`` float32, if asked).  ``t`` need not be a multiple of
    ``chunk``: the tail is padded with ``dt = 0`` positions, which leave
    the state as it is and whose outputs are cut off.
    """
    with jax.named_scope("ssd_scan"):
        return _ssd_scan(x, dt, A, B, C, D, chunk, return_final_state)


def _ssd_scan(x, dt, A, B, C, D, chunk, return_final_state):
    b, t, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    if h % g:
        raise ValueError(f"{h} heads do not divide into {g} groups")
    r = h // g
    f32 = jnp.float32
    pad = (-t) % chunk
    if pad:
        widths = lambda a: [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2)
        x, dt, B, C = (jnp.pad(a, widths(a)) for a in (x, dt, B, C))
    nc, L = (t + pad) // chunk, chunk

    xc = x.reshape(b, nc, L, g, r, p)
    Bc = B.reshape(b, nc, L, g, n)
    Cc = C.reshape(b, nc, L, g, n)
    dtc = dt.astype(f32).reshape(b, nc, L, g, r)
    # log-decay of one step, and its running sum inside the chunk
    a = dtc * A.astype(f32).reshape(g, r)
    a_cum = jnp.cumsum(a, axis=2)                       # [b, nc, L, g, r]

    # -- inside a chunk: y_l += sum_{s<=l} (C_l . B_s) exp(sum_{s<k<=l} a_k)
    #    dt_s x_s, as two matrix products around an [L, L] block a head
    scores = jnp.einsum("bclgn,bcsgn->bcgls", Cc, Bc,
                        preferred_element_type=f32)     # [b, nc, g, L, L]
    a_row = a_cum.transpose(0, 1, 3, 4, 2)              # [b, nc, g, r, L]
    seg = a_row[..., :, None] - a_row[..., None, :]     # l minus s
    causal = jnp.tril(jnp.ones((L, L), bool))
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))   # 0 above the diagonal
    dt_src = dtc.transpose(0, 1, 3, 4, 2)[..., None, :]  # dt of position s
    block = scores[:, :, :, None] * decay * dt_src      # [b, nc, g, r, L, L]
    y = jnp.einsum("bcgrls,bcsgrp->bclgrp", block.astype(x.dtype), xc,
                   preferred_element_type=f32)

    # -- what a chunk adds to the state by its end
    to_end = jnp.exp(a_cum[:, :, -1:] - a_cum) * dtc    # [b, nc, L, g, r]
    xw = xc.astype(f32) * to_end[..., None]
    states = jnp.einsum("bclgrp,bclgn->bcgrpn", xw, Bc.astype(f32),
                        precision=_HIGHEST).astype(STATE_DTYPE)

    # -- between chunks: the state a chunk starts from
    chunk_decay = jnp.exp(a_cum[:, :, -1])              # [b, nc, g, r]

    def step(carry, inp):
        add, dec = inp
        nxt = (carry * dec[..., None, None].astype(STATE_DTYPE)
               + add).astype(STATE_DTYPE)
        return nxt, carry

    start = jnp.zeros((b, g, r, p, n), STATE_DTYPE)
    final, entering = jax.lax.scan(
        step, start,
        (states.transpose(1, 0, 2, 3, 4, 5),
         chunk_decay.transpose(1, 0, 2, 3)),
    )
    entering = entering.transpose(1, 0, 2, 3, 4, 5)     # [b, nc, g, r, p, n]

    # -- what the entering state gives each position of the chunk
    y_off = jnp.einsum("bclgn,bcgrpn->bclgrp", Cc.astype(f32),
                       entering.astype(f32), precision=_HIGHEST)
    y = y + y_off * jnp.exp(a_cum)[..., None]

    y = y.reshape(b, nc * L, h, p)[:, :t]
    if D is not None:
        y = y + D.astype(f32)[:, None] * x[:, :t].astype(f32)
    y = y.astype(x.dtype)
    if return_final_state:
        return y, final.reshape(b, h, p, n).astype(f32)
    return y


__all__ = ["ssd_scan", "STATE_DTYPE"]
