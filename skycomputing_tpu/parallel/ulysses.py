"""Ulysses-style sequence parallelism: all-to-all head-parallel attention.

The second of the two standard long-context strategies (alongside
:mod:`.ring_attention`): instead of rotating key/value blocks around a ring,
one ``all_to_all`` re-shards the activations from sequence-parallel to
head-parallel — each device then holds the FULL sequence for ``H/S`` heads,
computes ordinary attention locally with no inner loop, and a second
``all_to_all`` restores sequence sharding.  Communication is two all-to-alls
of the activation size per attention call (vs S neighbor hops for the ring);
on a TPU torus the all-to-all rides ICI efficiently, and the local attention
keeps the full-softmax structure — which makes this variant the natural host
for score-level extras (relative-position biases, arbitrary masks) that an
online softmax cannot apply after the fact.

Requires ``num_heads`` and the sequence length divisible by the ``sp``
mesh-axis size.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis_name: str = "sp",
    causal: bool = False,
    scale: Optional[float] = None,
    bias: Optional[jax.Array] = None,
) -> jax.Array:
    """Exact attention over sequence-sharded q/k/v via head all-to-alls.

    Args:
        q, k, v: [batch, seq, heads, head_dim] global views, sharded on
            ``seq`` over ``axis_name``.
        bias: optional additive per-key bias [batch, seq] (padding mask),
            sequence-sharded like k.

    Returns [batch, seq, heads, head_dim], sequence-sharded like q.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    S = int(mesh.shape[axis_name])
    H = q.shape[2]
    if H % S != 0:
        raise ValueError(
            f"ulysses needs num_heads ({H}) divisible by the "
            f"{axis_name} axis size ({S})"
        )
    L = q.shape[1]
    if L % S != 0:
        raise ValueError(
            f"ulysses needs sequence length ({L}) divisible by the "
            f"{axis_name} axis size ({S})"
        )

    def local_fn(q_blk, k_blk, v_blk, bias_blk):
        # local: [B, L/S, H, D] -> all_to_all -> [B, L, H/S, D]
        def seq_to_heads(x):
            return lax.all_to_all(
                x, axis_name, split_axis=2, concat_axis=1, tiled=True
            )

        def heads_to_seq(x):
            return lax.all_to_all(
                x, axis_name, split_axis=1, concat_axis=2, tiled=True
            )

        qh = seq_to_heads(q_blk).astype(jnp.float32) * scale
        kh = seq_to_heads(k_blk).astype(jnp.float32)
        vh = seq_to_heads(v_blk).astype(jnp.float32)

        scores = jnp.einsum("bqhd,bkhd->bhqk", qh, kh)
        if bias_blk is not None:
            # bias is per-key over the FULL sequence: gather the shards
            full_bias = lax.all_gather(
                bias_blk, axis_name, axis=1, tiled=True
            ).astype(jnp.float32)
            scores = scores + full_bias[:, None, None, :]
        if causal:
            allowed = jnp.tril(jnp.ones((L, L), bool))
            scores = jnp.where(allowed[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, vh)
        return heads_to_seq(ctx.astype(q_blk.dtype))

    seq_spec = P(None, axis_name, None, None)
    bias_spec = P(None, axis_name)
    if bias is None:
        return jax.shard_map(
            lambda a, b, c: local_fn(a, b, c, None),
            mesh=mesh,
            in_specs=(seq_spec, seq_spec, seq_spec),
            out_specs=seq_spec,
            check_vma=False,
        )(q, k, v)
    return jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(seq_spec, seq_spec, seq_spec, bias_spec),
        out_specs=seq_spec,
        check_vma=False,
    )(q, k, v, bias)


__all__ = ["ulysses_attention"]
