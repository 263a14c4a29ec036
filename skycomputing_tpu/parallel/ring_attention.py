"""Ring attention: exact attention over sequence-sharded inputs.

Long-context capability the reference lacks entirely (its attention is
vanilla O(L^2) full softmax, ``scaelum/model/bert_layers.py:249-275``, with
seq fixed at 128).  Here the sequence axis is sharded across a ``('sp',)``
mesh axis; each device keeps its query block resident while key/value blocks
rotate around the ring via ``lax.ppermute`` over ICI neighbor links, and
softmax is accumulated online (flash-attention style running max / running
sum in float32), so attention over a sequence of length L costs O(L/S) HBM
per chip and never materializes the full score matrix.

The rotation count equals the ring size, communication is neighbor-only
(bandwidth-optimal on a TPU torus), and the whole thing is differentiable —
``jax.grad`` through the scan + ppermute yields the reverse ring
automatically.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def _online_block_update(o, m, l, scores, v_blk):
    """Fold one block of scores/values into the running softmax state."""
    blk_max = jnp.max(scores, axis=-1)                       # [B, H, Lq]
    new_m = jnp.maximum(m, blk_max)
    correction = jnp.exp(m - new_m)
    p = jnp.exp(scores - new_m[..., None])                   # [B, H, Lq, Lk]
    l = l * correction + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v_blk)
    o = o * correction.transpose(0, 2, 1)[..., None] + pv
    return o, new_m, l


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis_name: str = "sp",
    causal: bool = False,
    scale: Optional[float] = None,
    bias: Optional[jax.Array] = None,
) -> jax.Array:
    """Exact attention with q/k/v sharded on the sequence axis.

    Args:
        q, k, v: [batch, seq, heads, head_dim], sharded on ``seq`` over
            ``axis_name`` (global views; shard_map slices them).
        causal: apply a causal mask using *global* positions.
        bias: optional additive per-key bias [batch, seq] (padding masks,
            BERT's ``(1-mask)*-1e4``), sharded on ``seq`` like k; rotated
            around the ring alongside the key/value blocks.

    Returns [batch, seq, heads, head_dim], sequence-sharded like q.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    S = int(mesh.shape[axis_name])
    ring = [(i, (i + 1) % S) for i in range(S)]
    has_bias = bias is not None

    def local_fn(q_blk, k_blk, v_blk, bias_blk):
        # local shapes: [B, Lb, H, D]; bias [B, Lb]
        idx = lax.axis_index(axis_name)
        B, Lb, H, D = q_blk.shape
        q_f32 = q_blk.astype(jnp.float32) * scale

        o = jnp.zeros((B, Lb, H, D), jnp.float32)
        m = jnp.full((B, H, Lb), -jnp.inf, jnp.float32)
        l = jnp.zeros((B, H, Lb), jnp.float32)

        q_pos = idx * Lb + jnp.arange(Lb)

        def step(carry, i):
            o, m, l, k_cur, v_cur, b_cur = carry
            scores = jnp.einsum(
                "bqhd,bkhd->bhqk", q_f32, k_cur.astype(jnp.float32)
            )
            if has_bias:
                scores = scores + b_cur.astype(jnp.float32)[:, None, None, :]
            if causal:
                # after i rotations this device holds the block that
                # originated on device (idx - i) mod S
                src = jnp.mod(idx - i, S)
                k_pos = src * Lb + jnp.arange(Lb)
                allowed = q_pos[:, None] >= k_pos[None, :]
                scores = jnp.where(allowed[None, None], scores, -jnp.inf)
            o2, m2, l2 = _online_block_update(o, m, l, scores, v_cur)
            k_nxt = lax.ppermute(k_cur, axis_name, ring)
            v_nxt = lax.ppermute(v_cur, axis_name, ring)
            # rotate the bias with its key block only when one exists — a
            # dummy bias would cost a real collective per ring step
            b_nxt = (
                lax.ppermute(b_cur, axis_name, ring) if has_bias else b_cur
            )
            return (o2, m2, l2, k_nxt, v_nxt, b_nxt), None

        (o, m, l, _, _, _), _ = lax.scan(
            step, (o, m, l, k_blk, v_blk, bias_blk), jnp.arange(S)
        )
        # fully-masked rows (causal, early global positions) have l == 0
        denom = jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
        return (o / denom).astype(q_blk.dtype)

    seq_spec = P(None, axis_name, None, None)
    bias_spec = P(None, axis_name)
    if not has_bias:
        # zero-size placeholder keeps one code path; it is never read or
        # permuted (has_bias is trace-time static)
        bias = jnp.zeros((q.shape[0], q.shape[1]), jnp.float32)
    return jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(seq_spec, seq_spec, seq_spec, bias_spec),
        out_specs=seq_spec,
        check_vma=False,
    )(q, k, v, bias)


def full_attention_reference(q, k, v, causal=False, scale=None, bias=None):
    """Single-device O(L^2) reference for testing."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale,
        k.astype(jnp.float32),
    )
    if bias is not None:
        scores = scores + bias.astype(jnp.float32)[:, None, None, :]
    if causal:
        L = q.shape[1]
        allowed = jnp.tril(jnp.ones((L, L), bool))
        scores = jnp.where(allowed[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v).astype(q.dtype)


__all__ = ["ring_attention", "full_attention_reference"]
