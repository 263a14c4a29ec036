"""Fused blockwise attention (flash attention) as a Pallas TPU kernel.

The reference computes attention as materialized [B, H, L, L] score tensors
through torch matmul + softmax (``scaelum/model/bert_layers.py:249-275``) —
HBM-bound on TPU.  This kernel streams K/V blocks through VMEM with an
online-softmax accumulator (running max / running sum in float32), so the
score matrix never hits HBM and the MXU stays fed.

Forward is the Pallas kernel; backward is a ``jax.custom_vjp`` that
recomputes attention with plain XLA ops (exact same math, float32 softmax),
trading backward-pass memory for a simple, provably-matching gradient.  On
non-TPU backends the kernel runs in interpret mode, which is how the CPU
test suite validates it bit-for-bit against the reference softmax.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _flash_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, *, block_k: int,
                  scale: float):
    # q_ref block: [1, block_q, d]; k/v blocks: [1, L, d]; bias: [1, 1, L]
    # (bias keeps a singleton row so its block shape equals its array shape,
    # which Mosaic requires when the block is not (8, 128)-aligned)
    q = q_ref[0, :, :].astype(jnp.float32) * scale
    seq_len = k_ref.shape[1]
    block_q, head_dim = q.shape
    num_kb = seq_len // block_k

    def body(i, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        b = bias_ref[0, 0, pl.ds(i * block_k, block_k)].astype(jnp.float32)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ()))
        )  # [block_q, block_k]
        s = s + b[None, :]

        m_blk = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_blk)
        p = jnp.exp(s - m_new)
        correction = jnp.exp(m - m_new)
        l_new = l * correction + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * correction + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ()))
        )
        return m_new, l_new, acc_new

    m0 = jnp.full((block_q, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, head_dim), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, num_kb, body, (m0, l0, acc0))

    o_ref[0, :, :] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _flash_forward(
    q, k, v, bias, scale, block_q, block_k, interpret
):
    """q/k/v: [B, L, H, D]; bias: [B, L] additive (0 or -1e4 style)."""
    B, L, H, D = q.shape

    def pick_block(requested: int) -> int:
        # honor the request when it tiles L exactly; otherwise fall back to
        # the largest multiple-of-8 divisor of L <= requested (Mosaic wants
        # 8-aligned sublanes), and as a last resort one full-L block
        if L <= requested:
            return L
        if L % requested == 0:
            return requested
        for b in range(requested - requested % 8, 7, -8):
            if L % b == 0:
                return b
        if L > 1024:
            # a single full-L tile would blow VMEM; make the caller pad
            raise ValueError(
                f"seq len {L} has no 8-aligned divisor <= {requested}; "
                f"pad the sequence to a multiple of 128"
            )
        return L

    block_q = pick_block(block_q)
    block_k = pick_block(block_k)

    # [B, L, H, D] -> [B*H, L, D] rows so each grid cell owns one head
    def to_rows(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, L, D)

    q_r, k_r, v_r = to_rows(q), to_rows(k), to_rows(v)
    bias_r = jnp.repeat(bias, H, axis=0)[:, None, :]  # [B*H, 1, L]

    grid = (B * H, L // block_q)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, block_k=block_k, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, iq: (bh, iq, 0)),
            pl.BlockSpec((1, L, D), lambda bh, iq: (bh, 0, 0)),
            pl.BlockSpec((1, L, D), lambda bh, iq: (bh, 0, 0)),
            pl.BlockSpec((1, 1, L), lambda bh, iq: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda bh, iq: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, L, D), q.dtype),
        interpret=interpret,
    )(q_r, k_r, v_r, bias_r)

    return out.reshape(B, H, L, D).transpose(0, 2, 1, 3)


def _reference_attention(q, k, v, bias, scale):
    """Plain-XLA attention, float32 softmax — used for the backward pass."""
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale,
        k.astype(jnp.float32),
    )
    s = s + bias[:, None, None, :].astype(jnp.float32)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(
        q.dtype
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def flash_attention(
    q,
    k,
    v,
    bias,
    scale: Optional[float] = None,
    block_q: int = 256,
    block_k: int = 512,
    interpret: Optional[bool] = None,
):
    """Fused attention.  q/k/v: [B, L, H, D]; bias: [B, L] additive mask.

    ``interpret=None`` compiles on a TPU backend and interprets (slowly
    but exactly) everywhere else — the convenience the CPU test mesh runs
    on; a TPU run therefore never interprets unless asked to.  The default
    block sizes (bq=256/bk=512) come from an early v5e sweep that no
    artifact in the tree records: kernel time against the einsum path is
    not measured.  They clamp to L for shorter sequences.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _flash_forward(q, k, v, bias, scale, block_q, block_k, interpret)


def _fwd(q, k, v, bias, scale, block_q, block_k, interpret):
    out = flash_attention(q, k, v, bias, scale, block_q, block_k, interpret)
    return out, (q, k, v, bias)


def _bwd(scale, block_q, block_k, interpret, residuals, g):
    q, k, v, bias = residuals
    if scale is None:
        scale = q.shape[-1] ** -0.5

    def f(q, k, v, bias):
        return _reference_attention(q, k, v, bias, scale)

    _, vjp_fn = jax.vjp(f, q, k, v, bias)
    return vjp_fn(g)


flash_attention.defvjp(_fwd, _bwd)

__all__ = ["flash_attention"]
