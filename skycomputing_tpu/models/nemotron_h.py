"""Nemotron-H (hybrid Mamba-2 / mixture-of-experts / attention) as
pipeline-splittable units.

Written from the published ``config.json`` of
``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16`` (``model_type``
``nemotron_h``) and the Mamba-2 paper.  This module is NOT imported with
the package: the layer registry imports it the first time a config names
one of its units (``registry.LAYER.register_lazy``).

==========================  ===============  =========================
registered name             inputs           outputs
==========================  ===============  =========================
``NemotronHEmbeddings``     (input_ids,)     hidden [B, T, d]
``NemotronHBlock``          hidden           hidden
``NemotronHHead``           hidden           logits [B, T, V] float32
==========================  ===============  =========================

A block, every layer (``residual_in_fp32`` false, ``norm_eps`` 1e-5):

    x = x + mixer(RMSNorm(x))

with ONE mixer a layer, by the layer's character in
``hybrid_override_pattern`` (there is no separate feed-forward):

``M``  Mamba-2.  ``[z | xBC | dt] = x W_in`` (inner | inner + 2 G N |
       heads; no bias); ``xBC = silu(causal_depthwise_conv1d(xBC, kernel
       4, bias))``; split ``x_s`` [H heads x P], ``B``, ``C`` [G groups x
       N, a group serving H / G consecutive heads]; ``dt = softplus(dt +
       dt_bias)``; ``A = -exp(A_log)`` a head; ``h_t = exp(dt_t A)
       h_{t-1} + dt_t B_t x_t^T``, ``y_t = C_t h_t + D x_t`` computed in
       chunks of ``chunk_size`` (``ops/ssd.py``); ``y = RMSNorm_grouped(y
       * silu(z))`` over the G groups, with a weight; ``out = y W_out``.
``E``  Experts.  Router logits ``x W_g`` in float32, ``s = sigmoid``;
       top-``num_experts_per_tok`` of ``n_routed_experts`` chosen on ``s +
       e_score_correction_bias`` (``n_group`` 1: no group limit); weights
       ``s`` at the chosen, over their sum (``norm_topk_prob``), times
       ``routed_scaling_factor``; an expert is ``W_down relu(W_up x)^2``
       (``relu2``, no gate); one shared expert of the same form, added for
       every token.  The layer holds ``experts_held`` experts starting at
       ``experts_held_start``: it routes over all of them and computes its
       own experts' part, dropless (``ops/moe_dropless.py``).
``*``  Grouped-query causal attention, no biases and NO rotary embedding
       (the family takes its positions from the Mamba layers; the config's
       ``rope_theta`` is not applied by the published modelling code).

Compute dtype ``dtype`` (bfloat16) for the matrix products, float32
parameters, norms, softmax, router, decays and scan states.  Scopes in the
traced programs: ``ssd_scan``, ``moe_route``, ``moe_experts``,
``shared_expert``, ``gqa_attn``.  Counters: an ``E`` block sows
``counters/moe`` = ``[tokens to each held expert ..., pairs routed here,
pairs dropped]`` and ``counters/moe_blocks`` = ``[blocks of the expert
order the dropless layer walked, calls]`` (int32), which the pipeline
engine accumulates on the device (``PipelineModel.read_counters``), and
``last_route`` (the router's input and choices); an ``M`` block sows
``last_scan`` (the scan's inputs and final state of the call's last
sequence).  Of a ``last_`` name the engine keeps the latest call's
(``PipelineModel.last_sown``): the values the timed programs themselves
computed, for a reference to be held to.
"""

from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import flax.linen as nn

from ..ops.moe_dropless import traced_for_tpu, dropless_experts, route_top_k
from ..ops.ssd import ssd_scan
from ..registry import LAYER

_HIGHEST = jax.lax.Precision.HIGHEST


class NemotronHConfig:
    """The published keys that shape the model (defaults: Nemotron-3-Nano
    30B-A3B), plus what a chip's share needs: ``experts_held_start`` /
    ``experts_held`` (default: all of them)."""

    def __init__(
        self,
        vocab_size: int = 131072,
        hidden_size: int = 2688,
        hybrid_override_pattern: str =
            "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
        # M
        mamba_num_heads: int = 64,
        mamba_head_dim: int = 64,
        ssm_state_size: int = 128,
        n_groups: int = 8,
        conv_kernel: int = 4,
        chunk_size: int = 128,
        time_step_min: float = 0.001,
        time_step_max: float = 0.1,
        time_step_floor: float = 1e-4,
        # E
        n_routed_experts: int = 128,
        num_experts_per_tok: int = 6,
        moe_intermediate_size: int = 1856,
        moe_shared_expert_intermediate_size: int = 3712,
        norm_topk_prob: bool = True,
        routed_scaling_factor: float = 2.5,
        experts_held_start: int = 0,
        experts_held: Optional[int] = None,
        # *
        num_attention_heads: int = 32,
        num_key_value_heads: int = 2,
        head_dim: int = 128,
        # all
        norm_eps: float = 1e-5,
        num_hidden_layers: int = 52,
        initializer_range: float = 0.02,
        dtype: str = "bfloat16",
    ):
        values = dict(locals())
        values.pop("self")
        if values["experts_held"] is None:
            values["experts_held"] = n_routed_experts
        self.__dict__.update(values)
        if (experts_held_start < 0 or self.experts_held < 1
                or experts_held_start + self.experts_held > n_routed_experts):
            raise ValueError(
                f"experts held [{experts_held_start}, "
                f"{experts_held_start + self.experts_held}) are not a range "
                f"of the {n_routed_experts} routed experts"
            )
        if set(hybrid_override_pattern) - set("ME*"):
            raise ValueError(
                f"pattern {hybrid_override_pattern!r}: only M, E and * "
                f"layers are known"
            )

    @classmethod
    def from_dict(cls, data) -> "NemotronHConfig":
        if isinstance(data, NemotronHConfig):
            return data
        import inspect

        data = dict(data)
        if "n_routed_experts_published" in data:
            # a file that states a chip's share: ``n_routed_experts``
            # counts the experts HELD, the router keeps its published width
            data["experts_held"] = data["n_routed_experts"]
            data["n_routed_experts"] = data["n_routed_experts_published"]
        known = set(inspect.signature(cls.__init__).parameters) - {"self"}
        return cls(**{k: v for k, v in data.items() if k in known})

    def to_dict(self) -> dict:
        return dict(self.__dict__)

    @property
    def mamba_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_width(self) -> int:
        return self.mamba_inner + 2 * self.n_groups * self.ssm_state_size


def _cfg(config) -> NemotronHConfig:
    return NemotronHConfig.from_dict(config)


def rms_norm(x, weight, eps: float, groups: int = 1):
    """RMSNorm in float32 over the last axis, or over ``groups`` equal
    slices of it; the weight spans the whole axis."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    shape = x.shape
    x = x.reshape(*shape[:-1], groups, shape[-1] // groups)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x.reshape(shape) * weight.astype(jnp.float32)).astype(dtype)


def _linear(module: nn.Module, name: str, x, features: int, cfg,
            scale: float = 1.0):
    """``x W`` with a float32 parameter ``[in, out]``, no bias, in the
    compute dtype with float32 accumulation."""
    w = module.param(
        name, nn.initializers.normal(cfg.initializer_range * scale),
        (x.shape[-1], features), jnp.float32,
    )
    dtype = jnp.dtype(cfg.dtype)
    return jnp.dot(x.astype(dtype), w.astype(dtype),
                   preferred_element_type=jnp.float32).astype(dtype)


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def _sow_last(module: nn.Module, name: str, values: dict) -> None:
    """Sow ``values`` into ``counters`` under a ``last_`` name: the engine
    keeps the latest call's, in place of the one before."""
    module.sow("counters", name, values,
               init_fn=lambda: jax.tree_util.tree_map(jnp.zeros_like, values),
               reduce_fn=lambda old, new: new)


class Mamba2Mixer(nn.Module):
    config: Any

    @nn.compact
    def __call__(self, x):
        cfg = _cfg(self.config)
        H, P = cfg.mamba_num_heads, cfg.mamba_head_dim
        G, N, K = cfg.n_groups, cfg.ssm_state_size, cfg.conv_kernel
        inner, conv_w = cfg.mamba_inner, cfg.conv_width
        b, t, _ = x.shape

        zxbcdt = _linear(self, "in_proj", x, inner + conv_w + H, cfg)
        z, xbc, dt = jnp.split(zxbcdt, [inner, inner + conv_w], axis=-1)

        # causal depthwise convolution: tap j reads position t - (K-1) + j
        bound = 1.0 / math.sqrt(K)
        uniform = lambda key, shape: jax.random.uniform(
            key, shape, jnp.float32, -bound, bound)
        conv_weight = self.param("conv_weight", uniform, (K, conv_w))
        conv_bias = self.param("conv_bias", uniform, (conv_w,))
        padded = jnp.pad(xbc, [(0, 0), (K - 1, 0), (0, 0)])
        conv = conv_bias.astype(jnp.float32)
        for j in range(K):
            conv = conv + padded[:, j:j + t].astype(jnp.float32) \
                * conv_weight[j]
        xbc = jax.nn.silu(conv).astype(x.dtype)
        xs, B, C = jnp.split(xbc, [inner, inner + G * N], axis=-1)

        def dt_bias_init(key, shape):
            # Mamba-2's: dt log-uniform in [time_step_min, time_step_max],
            # floored, stored as its inverse softplus
            u = jax.random.uniform(key, shape, jnp.float32)
            dt0 = jnp.exp(u * (math.log(cfg.time_step_max)
                               - math.log(cfg.time_step_min))
                          + math.log(cfg.time_step_min))
            dt0 = jnp.maximum(dt0, cfg.time_step_floor)
            return dt0 + jnp.log(-jnp.expm1(-dt0))

        dt_bias = self.param("dt_bias", dt_bias_init, (H,))
        A_log = self.param(
            "A_log",
            lambda key, shape: jnp.log(jax.random.uniform(
                key, shape, jnp.float32, 1.0, 16.0)),
            (H,),
        )
        D = self.param("D", nn.initializers.ones, (H,), jnp.float32)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
        xs, B, C = (xs.reshape(b, t, H, P), B.reshape(b, t, G, N),
                    C.reshape(b, t, G, N))
        y, state = ssd_scan(xs, dt, -jnp.exp(A_log), B, C, D,
                            chunk=cfg.chunk_size, return_final_state=True)
        y = y.reshape(b, t, inner)
        # what the scan was handed and the state it ended in, of the last
        # sequence of the call: a reference recurrence over the same inputs
        # holds the timed program's own state to its precision
        _sow_last(self, "last_scan", dict(
            x=xs[-1], dt=dt[-1], B=B[-1], C=C[-1], state=state[-1]))

        norm_weight = self.param("norm_weight", nn.initializers.ones,
                                 (inner,), jnp.float32)
        y = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        y = rms_norm(y, norm_weight, cfg.norm_eps, groups=G)
        # rescale_prenorm_residual: the family scales out_proj alone
        return _linear(self, "out_proj", y, cfg.hidden_size, cfg,
                       scale=1.0 / math.sqrt(cfg.num_hidden_layers))


def moe_route(tokens, gate, bias, cfg):
    """The router: logits in float32 (operands upcast, the product at
    ``Precision.HIGHEST``: a default float32 product on a TPU rounds its
    operands to bfloat16), then sigmoid top-k.  ``(indices, weights)``."""
    cfg = _cfg(cfg)
    with jax.named_scope("moe_route"):
        logits = jnp.dot(tokens.astype(jnp.float32), gate,
                         precision=_HIGHEST)
        return route_top_k(
            logits, bias, cfg.num_experts_per_tok,
            norm_topk_prob=cfg.norm_topk_prob,
            scaling_factor=cfg.routed_scaling_factor,
        )


class MoeMixer(nn.Module):
    config: Any

    @nn.compact
    def __call__(self, x):
        cfg = _cfg(self.config)
        d, f = cfg.hidden_size, cfg.moe_intermediate_size
        E = cfg.experts_held
        init = nn.initializers.normal(cfg.initializer_range)
        tokens = x.reshape(-1, d)

        gate = self.param("router", init, (d, cfg.n_routed_experts),
                          jnp.float32)
        bias = self.param("e_score_correction_bias", nn.initializers.zeros,
                          (cfg.n_routed_experts,), jnp.float32)
        idx, weights = moe_route(tokens, gate, bias, cfg)
        # the router's input and choices of the call, for the same reason
        _sow_last(self, "last_route", dict(tokens=tokens, idx=idx))
        w_up = self.param("experts_up", init, (E, d, f), jnp.float32)
        w_down = self.param("experts_down", init, (E, f, d), jnp.float32)
        # opens the scope ``moe_experts`` itself, inside its loop's body
        routed, counts, blocks = dropless_experts(
            tokens, idx, weights, w_up, w_down,
            held_start=cfg.experts_held_start,
            num_experts=cfg.n_routed_experts,
        )
        walked = jnp.stack([blocks, jnp.ones_like(blocks)])
        for name, value in (("moe", counts), ("moe_blocks", walked)):
            self.sow("counters", name, value,
                     init_fn=lambda value=value: jnp.zeros_like(value),
                     reduce_fn=lambda total, new: total + new)
        with jax.named_scope("shared_expert"):
            hidden = relu2(_linear(
                self, "shared_up", tokens,
                cfg.moe_shared_expert_intermediate_size, cfg))
            shared = _linear(self, "shared_down", hidden, d, cfg)
        out = routed + shared.astype(jnp.float32)
        return out.astype(x.dtype).reshape(x.shape)


def causal_gqa(q, k, v, *, impl: Optional[str] = None):
    """Causal attention, ``q`` [b, t, Hq, D] over ``k`` / ``v`` [b, t, Hkv,
    D] (each key/value head serving ``Hq // Hkv`` consecutive query
    heads), softmax in float32.  On a TPU the Pallas flash-attention kernel
    that ships with JAX (forward and backward; the scores never reach
    HBM); elsewhere the masked softmax written out."""
    b, t, hq, dh = q.shape
    rep = hq // k.shape[2]
    scale = dh ** -0.5
    if impl is None:
        impl = "pallas" if traced_for_tpu() and t % 128 == 0 else "xla"
    with jax.named_scope("gqa_attn"):
        if impl == "pallas":
            from jax.experimental.pallas.ops.tpu.flash_attention import (
                BlockSizes,
                flash_attention,
            )

            # tiles of 512 where the length allows (the kernel's default of
            # 128 took 9.7 ms a forward at 4096 positions on the v5e)
            tile = next(n for n in (512, 256, 128) if t % n == 0)
            heads_first = lambda a: a.transpose(0, 2, 1, 3)
            out = flash_attention(
                heads_first(q),
                heads_first(jnp.repeat(k, rep, axis=2)),
                heads_first(jnp.repeat(v, rep, axis=2)),
                causal=True, sm_scale=scale,
                block_sizes=BlockSizes(
                    block_q=tile, block_k_major=tile, block_k=tile,
                    block_b=1, block_q_major_dkv=tile,
                    block_k_major_dkv=tile, block_k_dkv=tile,
                    block_q_dkv=tile, block_k_major_dq=tile,
                    block_k_dq=tile, block_q_dq=tile,
                ),
            )
            return out.transpose(0, 2, 1, 3)
        qg = q.reshape(b, t, k.shape[2], rep, dh)
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k,
                            preferred_element_type=jnp.float32) * scale
        mask = jnp.tril(jnp.ones((t, t), bool))
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        out = jnp.einsum("bgrqk,bkgd->bqgrd", probs.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        return out.reshape(b, t, hq, dh).astype(q.dtype)


class AttentionMixer(nn.Module):
    config: Any

    @nn.compact
    def __call__(self, x):
        cfg = _cfg(self.config)
        Hq, Hkv, dh = (cfg.num_attention_heads, cfg.num_key_value_heads,
                       cfg.head_dim)
        b, t, _ = x.shape
        q = _linear(self, "q_proj", x, Hq * dh, cfg).reshape(b, t, Hq, dh)
        k = _linear(self, "k_proj", x, Hkv * dh, cfg).reshape(b, t, Hkv, dh)
        v = _linear(self, "v_proj", x, Hkv * dh, cfg).reshape(b, t, Hkv, dh)
        out = causal_gqa(q, k, v).reshape(b, t, Hq * dh)
        return _linear(self, "o_proj", out, cfg.hidden_size, cfg)


_MIXERS = {"M": Mamba2Mixer, "E": MoeMixer, "*": AttentionMixer}


@LAYER.register_module
class NemotronHEmbeddings(nn.Module):
    config: Any

    @nn.compact
    def __call__(self, input_ids):
        cfg = _cfg(self.config)
        table = self.param(
            "embedding", nn.initializers.normal(cfg.initializer_range),
            (cfg.vocab_size, cfg.hidden_size), jnp.float32,
        )
        return jnp.take(table, input_ids, axis=0).astype(
            jnp.dtype(cfg.dtype))


@LAYER.register_module
class NemotronHBlock(nn.Module):
    """``x + mixer(RMSNorm(x))``; ``mixer`` is ``"M"``, ``"E"`` or ``"*"``."""

    config: Any
    mixer: str = "M"

    @property
    def has_counters(self) -> bool:
        """Does this layer sow into ``counters`` (the engine then runs the
        stage's counted forward)?"""
        return self.mixer in "ME"

    @nn.compact
    def __call__(self, x):
        cfg = _cfg(self.config)
        weight = self.param("norm_weight", nn.initializers.ones,
                            (cfg.hidden_size,), jnp.float32)
        normed = rms_norm(x, weight, cfg.norm_eps)
        return x + _MIXERS[self.mixer](self.config, name="mixer")(normed)


@LAYER.register_module
class NemotronHHead(nn.Module):
    """Final RMSNorm and the untied output head over the vocabulary
    (slice) held here; logits in float32."""

    config: Any

    @nn.compact
    def __call__(self, x):
        cfg = _cfg(self.config)
        weight = self.param("norm_weight", nn.initializers.ones,
                            (cfg.hidden_size,), jnp.float32)
        x = rms_norm(x, weight, cfg.norm_eps)
        head = self.param(
            "lm_head", nn.initializers.normal(cfg.initializer_range),
            (cfg.hidden_size, cfg.vocab_size), jnp.float32,
        )
        dtype = jnp.dtype(cfg.dtype)
        return jnp.dot(x.astype(dtype), head.astype(dtype),
                       preferred_element_type=jnp.float32)


def nemotron_h_layer_configs(config) -> list:
    """Embedding, one block a character of the pattern, head: the list
    ``cfg.model_config`` holds and the allocator partitions."""
    cfg = _cfg(config)
    if len(cfg.hybrid_override_pattern) != cfg.num_hidden_layers:
        raise ValueError(
            f"pattern of {len(cfg.hybrid_override_pattern)} layers, "
            f"num_hidden_layers {cfg.num_hidden_layers}"
        )
    as_dict = cfg.to_dict()
    return (
        [dict(layer_type="NemotronHEmbeddings", config=as_dict)]
        + [dict(layer_type="NemotronHBlock", config=as_dict, mixer=kind)
           for kind in cfg.hybrid_override_pattern]
        + [dict(layer_type="NemotronHHead", config=as_dict)]
    )


__all__ = [
    "NemotronHConfig",
    "NemotronHEmbeddings",
    "NemotronHBlock",
    "NemotronHHead",
    "Mamba2Mixer",
    "MoeMixer",
    "AttentionMixer",
    "causal_gqa",
    "moe_route",
    "rms_norm",
    "relu2",
    "nemotron_h_layer_configs",
]
