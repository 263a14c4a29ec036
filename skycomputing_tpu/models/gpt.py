"""GPT-style causal LM decomposed into pipeline-splittable units.

The reference ships only BERT and ResNet zoos; this family demonstrates the
framework's generality on decoder-only models using the exact same
registry/LayerStack/allocator machinery.  Decomposition mirrors the BERT
zoo's granularity so profiling and allocation work identically:

==========================  =======================================  ==================
registered name             inputs                                   outputs
==========================  =======================================  ==================
``GptEmbeddings``           (input_ids,)                             hidden
``GptBlock_Attn``           hidden                                   hidden
``GptBlock_Mlp``            hidden                                   hidden
``GptLmHead``               hidden                                   logits [B, L, V]
==========================  =======================================  ==================

TPU-first details: pre-LayerNorm blocks, causal attention with a float32
softmax (optionally ring attention over an 'sp' mesh for long context),
bfloat16 compute, weight-tied LM head optional.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
import flax.linen as nn

from ..registry import LAYER
from .bert import ACT2FN


class GptConfig:
    def __init__(
        self,
        vocab_size: int = 50257,
        hidden_size: int = 768,
        num_hidden_layers: int = 12,
        num_attention_heads: int = 12,
        intermediate_size: Optional[int] = None,
        max_position_embeddings: int = 1024,
        hidden_act: str = "gelu",
        dropout_prob: float = 0.1,
        initializer_range: float = 0.02,
        dtype: str = "bfloat16",
    ):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.intermediate_size = intermediate_size or 4 * hidden_size
        self.max_position_embeddings = max_position_embeddings
        self.hidden_act = hidden_act
        self.dropout_prob = dropout_prob
        self.initializer_range = initializer_range
        self.dtype = dtype

    @classmethod
    def from_dict(cls, data) -> "GptConfig":
        if isinstance(data, GptConfig):
            return data
        data = dict(data)
        import inspect

        known = set(inspect.signature(cls.__init__).parameters) - {"self"}
        # route known keys through __init__ so derived defaults (e.g.
        # intermediate_size = 4*hidden_size) are computed from the dict's
        # values, not the class defaults
        cfg = cls(**{k: v for k, v in data.items() if k in known})
        for k, v in data.items():
            if k not in known:
                setattr(cfg, k, v)
        return cfg

    def to_dict(self) -> dict:
        return dict(self.__dict__)


def _gcfg(config) -> GptConfig:
    return GptConfig.from_dict(config)


def _gdense(cfg: GptConfig, features: int,
            name: Optional[str] = None) -> nn.Dense:
    # name is passed in compact modules; setup-style modules name by
    # attribute assignment and must omit it
    kwargs = {} if name is None else {"name": name}
    return nn.Dense(
        features,
        dtype=jnp.dtype(cfg.dtype),
        param_dtype=jnp.float32,
        kernel_init=nn.initializers.normal(cfg.initializer_range),
        **kwargs,
    )


@LAYER.register_module
class GptEmbeddings(nn.Module):
    """Token + learned position embeddings.

    ``setup``-style so the same submodules back both the full forward and
    the KV-cache ``decode`` path; attribute names keep the param tree
    identical to the original compact layout (``wte``/``wpe``).
    """

    config: Any
    deterministic: bool = False

    def setup(self):
        cfg = _gcfg(self.config)
        dtype = jnp.dtype(cfg.dtype)
        init = nn.initializers.normal(cfg.initializer_range)
        self.wte = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=dtype,
                            embedding_init=init)
        self.wpe = nn.Embed(cfg.max_position_embeddings, cfg.hidden_size,
                            dtype=dtype, embedding_init=init)
        self.drop = nn.Dropout(cfg.dropout_prob)

    def __call__(self, input_ids):
        cfg = _gcfg(self.config)
        seq_len = input_ids.shape[1]
        if seq_len > cfg.max_position_embeddings:
            raise ValueError(
                f"sequence length {seq_len} exceeds "
                f"max_position_embeddings={cfg.max_position_embeddings}"
            )
        hidden = self.wte(input_ids) + self.wpe(
            jnp.arange(seq_len, dtype=jnp.int32)[None, :]
        )
        return self.drop(hidden, deterministic=self.deterministic)

    def decode(self, input_ids, index):
        """Embed ``input_ids`` [B, Lq] occupying positions index..index+Lq-1.

        ``index`` may be a scalar (all rows at the same offset) or a [B]
        vector (continuous batching: every slot at its own position).
        Dropout is never applied (decoding is inference).
        """
        from ..serving.kv_cache import decode_positions

        positions = decode_positions(index, input_ids.shape[1])
        return self.wte(input_ids) + self.wpe(positions)


@LAYER.register_module
class GptBlock_Attn(nn.Module):
    """Pre-LN causal self-attention half of a transformer block."""

    config: Any
    deterministic: bool = False
    mesh: Any = None  # optional 'sp' ring for long context
    axis_name: str = "sp"

    def setup(self):
        cfg = _gcfg(self.config)
        self.ln_1 = nn.LayerNorm(epsilon=1e-5, dtype=jnp.float32)
        self.q_proj = _gdense(cfg, cfg.hidden_size)
        self.k_proj = _gdense(cfg, cfg.hidden_size)
        self.v_proj = _gdense(cfg, cfg.hidden_size)
        self.c_proj = _gdense(cfg, cfg.hidden_size)
        self.drop = nn.Dropout(cfg.dropout_prob)

    def _qkv(self, hidden):
        cfg = _gcfg(self.config)
        n_heads = cfg.num_attention_heads
        head_dim = cfg.hidden_size // n_heads
        x = self.ln_1(hidden).astype(jnp.dtype(cfg.dtype))

        def split_heads(t):
            return t.reshape(t.shape[0], t.shape[1], n_heads, head_dim)

        return (split_heads(self.q_proj(x)), split_heads(self.k_proj(x)),
                split_heads(self.v_proj(x)))

    def __call__(self, hidden):
        cfg = _gcfg(self.config)
        dtype = jnp.dtype(cfg.dtype)
        head_dim = cfg.hidden_size // cfg.num_attention_heads
        q, k, v = self._qkv(hidden)

        if self.mesh is not None:
            from ..parallel.ring_attention import ring_attention

            ctx = ring_attention(q, k, v, self.mesh,
                                 axis_name=self.axis_name, causal=True)
        else:
            scores = jnp.einsum("blhd,bmhd->bhlm", q, k) / jnp.sqrt(
                jnp.asarray(head_dim, dtype)
            )
            L = q.shape[1]
            causal = jnp.tril(jnp.ones((L, L), bool))
            scores = jnp.where(causal[None, None], scores, -jnp.inf)
            probs = jax.nn.softmax(
                scores.astype(jnp.float32), axis=-1
            ).astype(dtype)
            ctx = jnp.einsum("bhlm,bmhd->blhd", probs, v)

        ctx = ctx.reshape(ctx.shape[0], ctx.shape[1], cfg.hidden_size)
        out = self.drop(self.c_proj(ctx), deterministic=self.deterministic)
        return hidden + out

    def decode(self, hidden, k_cache, v_cache, index):
        """One incremental step: update the fixed-shape KV cache, attend.

        ``hidden``: [B, Lq, H] new positions index..index+Lq-1;
        ``k_cache``/``v_cache``: [B, max_len, heads, head_dim] slabs
        (see ``serving/kv_cache.py`` — the one KV-cache implementation);
        ``index`` scalar or [B] per-slot vector.
        Returns (new_hidden, k_cache, v_cache).
        """
        from ..serving.kv_cache import decode_visibility, update_kv_cache

        cfg = _gcfg(self.config)
        dtype = jnp.dtype(cfg.dtype)
        head_dim = cfg.hidden_size // cfg.num_attention_heads
        q, k_new, v_new = self._qkv(hidden)

        k_cache, v_cache = update_kv_cache(
            k_cache, v_cache, k_new, v_new, index
        )

        scores = jnp.einsum(
            "blhd,bmhd->bhlm", q, k_cache.astype(dtype)
        ) / jnp.sqrt(jnp.asarray(head_dim, dtype))
        Lq, max_len = q.shape[1], k_cache.shape[1]
        visible = decode_visibility(index, Lq, max_len)  # [B|1, Lq, max]
        scores = jnp.where(visible[:, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(
            dtype
        )
        ctx = jnp.einsum("bhlm,bmhd->blhd", probs, v_cache.astype(dtype))
        ctx = ctx.reshape(ctx.shape[0], ctx.shape[1], cfg.hidden_size)
        return hidden + self.c_proj(ctx), k_cache, v_cache

    def decode_paged(
        self, hidden, k_slab, v_slab, page_table, index, valid_len,
        attn_impl: str = "xla",
    ):
        """One incremental step against PAGED slabs (PagedAttention).

        ``hidden``: [R, Lq, H] new positions index..index+Lq-1 per row;
        ``k_slab``/``v_slab``: [num_pages, page_size, heads * head_dim]
        physical page pools shared by every row — plain arrays, or
        ``serving/kv_cache.QuantizedPages`` (int8 values + scale slab);
        ``page_table``: [R, table_width] logical->physical map
        (sentinel-padded); ``index``/``valid_len``: [R] per-row start
        and true end positions (pad-tail writes drop; see
        ``serving/kv_cache.paged_update_kv``).

        ``attn_impl`` picks the attention body behind one contract:

        - ``"xla"`` (reference): gather the virtual per-row views
          (materialized in HBM — cost scales with the TABLE width) and
          run the masked float32 softmax, exactly :meth:`decode`'s math;
        - ``"pallas"``: the fused kernel (``ops/paged_attention.py``)
          walks the page table inside the kernel, streaming pages
          through online-softmax accumulation, so the virtual view
          never materializes.  fp outputs agree with the reference to
          float32 roundoff (greedy streams token-identical); int8 pages
          dequantize in-kernel.

        Both impls share the one visibility definition — logical
        position v visible to query q iff ``v <= index + q`` — so a
        sentinel-clamped or stale page reads as masked garbage exactly
        like a freed row's tail in :meth:`decode`.  Returns
        (new_hidden, k_slab, v_slab).
        """
        from ..serving.kv_cache import (
            QuantizedPages,
            decode_visibility,
            gather_kv_pages,
            paged_update_kv,
        )

        cfg = _gcfg(self.config)
        dtype = jnp.dtype(cfg.dtype)
        head_dim = cfg.hidden_size // cfg.num_attention_heads
        q, k_new, v_new = self._qkv(hidden)

        k_slab, v_slab = paged_update_kv(
            k_slab, v_slab, k_new, v_new, page_table, index, valid_len
        )
        if attn_impl == "pallas":
            from ..ops.paged_attention import paged_attention

            if isinstance(k_slab, QuantizedPages):
                ctx = paged_attention(
                    q, k_slab.values, v_slab.values, page_table, index,
                    k_scale=k_slab.scale, v_scale=v_slab.scale,
                )
            else:
                ctx = paged_attention(
                    q, k_slab, v_slab, page_table, index
                )
            ctx = ctx.astype(dtype)
        elif attn_impl == "xla":
            k_virt, v_virt = gather_kv_pages(
                k_slab, v_slab, page_table, cfg.num_attention_heads
            )

            scores = jnp.einsum(
                "blhd,bmhd->bhlm", q, k_virt.astype(dtype)
            ) / jnp.sqrt(jnp.asarray(head_dim, dtype))
            Lq, virt_len = q.shape[1], k_virt.shape[1]
            visible = decode_visibility(index, Lq, virt_len)
            scores = jnp.where(visible[:, None], scores, -jnp.inf)
            probs = jax.nn.softmax(
                scores.astype(jnp.float32), axis=-1
            ).astype(dtype)
            ctx = jnp.einsum(
                "bhlm,bmhd->blhd", probs, v_virt.astype(dtype)
            )
        else:
            raise ValueError(
                f"attn_impl must be 'xla' or 'pallas', got {attn_impl!r}"
            )
        ctx = ctx.reshape(ctx.shape[0], ctx.shape[1], cfg.hidden_size)
        return hidden + self.c_proj(ctx), k_slab, v_slab


@LAYER.register_module
class GptBlock_Mlp(nn.Module):
    """Pre-LN MLP half of a transformer block."""

    config: Any
    deterministic: bool = False

    @nn.compact
    def __call__(self, hidden):
        cfg = _gcfg(self.config)
        act = ACT2FN[cfg.hidden_act]
        x = nn.LayerNorm(epsilon=1e-5, dtype=jnp.float32, name="ln_2")(
            hidden
        ).astype(jnp.dtype(cfg.dtype))
        x = act(_gdense(cfg, cfg.intermediate_size, "c_fc")(x))
        x = _gdense(cfg, cfg.hidden_size, "c_proj")(x)
        x = nn.Dropout(cfg.dropout_prob)(x, deterministic=self.deterministic)
        return hidden + x


@LAYER.register_module
class GptBlock_MoeMlp(nn.Module):
    """Pre-LN mixture-of-experts MLP half of a transformer block.

    Switch/GShard-style: top-k router, fixed-capacity einsum dispatch
    (``ops/moe.py``), experts stacked on a leading axis so expert
    parallelism is a ``P('ep', ...)`` sharding annotation on the expert
    params.  The load-balance aux loss is sown into the 'intermediates'
    collection (``aux_loss``); training configs add it to the task loss
    via ``mutable=['intermediates']``.
    """

    config: Any
    num_experts: int = 8
    top_k: int = 1
    capacity_factor: float = 1.25
    deterministic: bool = False
    # return (hidden, aux) instead of sowing — for callers whose tracing
    # context cannot harvest mutable collections (scan/shard_map pipeline
    # stages, skycomputing_tpu/parallel/spmd_gpt.py)
    return_aux: bool = False

    @nn.compact
    def __call__(self, hidden):
        from ..ops.moe import (
            moe_dispatch_combine,
            router_probs,
            top_k_dispatch,
        )

        cfg = _gcfg(self.config)
        dtype = jnp.dtype(cfg.dtype)
        act = ACT2FN[cfg.hidden_act]
        E, H, I = self.num_experts, cfg.hidden_size, cfg.intermediate_size

        x = nn.LayerNorm(epsilon=1e-5, dtype=jnp.float32, name="ln_2")(
            hidden
        ).astype(dtype)
        B, L, _ = x.shape
        tokens = x.reshape(B * L, H)
        T = B * L
        capacity = max(1, int(np.ceil(T / E * self.capacity_factor)))

        router = self.param(
            "router", nn.initializers.normal(cfg.initializer_range), (H, E),
            jnp.float32,
        )
        init = nn.initializers.normal(cfg.initializer_range)
        w1 = self.param("w1", init, (E, H, I), jnp.float32)
        b1 = self.param("b1", nn.initializers.zeros, (E, I), jnp.float32)
        w2 = self.param("w2", init, (E, I, H), jnp.float32)
        b2 = self.param("b2", nn.initializers.zeros, (E, H), jnp.float32)

        probs = router_probs(tokens, router)
        dispatch, combine, aux = top_k_dispatch(probs, capacity, self.top_k)
        self.sow("intermediates", "aux_loss", aux)

        def experts(buf):  # [E, C, H] -> [E, C, H]
            h = act(
                jnp.einsum("ech,ehi->eci", buf, w1.astype(dtype))
                + b1[:, None, :].astype(dtype)
            )
            return (
                jnp.einsum("eci,eih->ech", h, w2.astype(dtype))
                + b2[:, None, :].astype(dtype)
            )

        out = moe_dispatch_combine(tokens, dispatch, combine, experts)
        out = out.reshape(B, L, H).astype(dtype)
        out = nn.Dropout(cfg.dropout_prob)(
            out, deterministic=self.deterministic
        )
        if self.return_aux:
            return hidden + out, aux
        return hidden + out


@LAYER.register_module
class GptLmHead(nn.Module):
    """Final LayerNorm + vocabulary projection."""

    config: Any
    deterministic: bool = False

    @nn.compact
    def __call__(self, hidden):
        cfg = _gcfg(self.config)
        x = nn.LayerNorm(epsilon=1e-5, dtype=jnp.float32, name="ln_f")(hidden)
        logits = nn.Dense(
            cfg.vocab_size,
            dtype=jnp.dtype(cfg.dtype),
            param_dtype=jnp.float32,
            kernel_init=nn.initializers.normal(cfg.initializer_range),
            name="lm_head",
        )(x.astype(jnp.dtype(cfg.dtype)))
        return logits.astype(jnp.float32)


def gpt_layer_configs(
    config: Any,
    num_blocks: Optional[int] = None,
    deterministic: bool = False,
    mesh: Any = None,
    moe_every: int = 0,
    num_experts: int = 8,
    moe_top_k: int = 1,
    moe_capacity_factor: float = 1.25,
) -> list:
    """Full layer-config list: embeddings + blocks x (attn, mlp) + LM head.

    ``moe_every=n`` replaces every n-th block's MLP with a
    :class:`GptBlock_MoeMlp` (GShard-style interleaving; 0 = dense only).
    """
    cfg = _gcfg(config)
    if num_blocks is None:
        num_blocks = cfg.num_hidden_layers
    blocks = []
    for b in range(num_blocks):
        blocks.append(
            dict(layer_type="GptBlock_Attn", config=cfg.to_dict(),
                 deterministic=deterministic, mesh=mesh)
        )
        if moe_every and (b + 1) % moe_every == 0:
            blocks.append(
                dict(layer_type="GptBlock_MoeMlp", config=cfg.to_dict(),
                     num_experts=num_experts, top_k=moe_top_k,
                     capacity_factor=moe_capacity_factor,
                     deterministic=deterministic)
            )
            continue
        blocks.append(
            dict(layer_type="GptBlock_Mlp", config=cfg.to_dict(),
                 deterministic=deterministic)
        )
    return (
        [dict(layer_type="GptEmbeddings", config=cfg.to_dict(),
              deterministic=deterministic)]
        + blocks
        + [dict(layer_type="GptLmHead", config=cfg.to_dict(),
                deterministic=deterministic)]
    )


# re-exported from the loss registry (registered there as "CausalLmLoss")
from ..ops.losses import causal_lm_loss  # noqa: E402


def generate(
    forward_fn,
    prompt,
    max_new_tokens: int,
    context_length: int,
    temperature: float = 0.0,
    rng: Optional[jax.Array] = None,
    pad_id: int = 0,
):
    """Autoregressive decoding against any fixed-shape forward function.

    ``forward_fn(input_ids) -> logits [B, L, V]`` — e.g.
    ``lambda ids: pipeline_model.forward((ids,))`` or a jitted monolithic
    apply.  The prompt is right-padded to ``context_length`` so the forward
    keeps one compiled shape; greedy when ``temperature == 0``, else
    categorical sampling.
    """
    import numpy as np

    prompt = np.asarray(prompt)
    if prompt.ndim == 1:
        prompt = prompt[None]
    batch, start_len = prompt.shape
    if start_len + max_new_tokens > context_length:
        raise ValueError(
            f"prompt ({start_len}) + new tokens ({max_new_tokens}) exceed "
            f"context_length={context_length}"
        )

    tokens = np.full((batch, context_length), pad_id, dtype=np.int32)
    tokens[:, :start_len] = prompt
    length = start_len
    for step in range(max_new_tokens):
        logits = np.asarray(forward_fn(tokens))
        next_logits = logits[:, length - 1]
        if temperature <= 0.0:
            nxt = next_logits.argmax(axis=-1)
        else:
            if rng is None:
                rng = jax.random.key(0)
            rng, sub = jax.random.split(rng)
            nxt = np.asarray(
                jax.random.categorical(
                    sub, jnp.asarray(next_logits) / temperature, axis=-1
                )
            )
        tokens[:, length] = nxt.astype(np.int32)
        length += 1
    return tokens[:, :length]


def decode_modules(modules) -> list:
    """Validated, dropout-free module list for KV-cache decoding.

    The shared preparation step for every decoding consumer (the
    single-request :class:`CachedGptDecoder` and the serving engine's
    stage slices): ring attention is rejected (its ppermute schedule has
    no incremental form) and any module with a live ``deterministic``
    knob is cloned with dropout forced off.
    """
    prepared = []
    for m in list(getattr(modules, "modules", modules)):
        if isinstance(m, GptBlock_Attn) and m.mesh is not None:
            raise ValueError(
                "cached decoding does not support ring attention; "
                "build the stack with mesh=None"
            )
        if hasattr(m, "deterministic") and not m.deterministic:
            m = m.clone(deterministic=True)
        prepared.append(m)
    return prepared


def attn_indices(modules) -> list:
    """Positions of the KV-cache-bearing units in a module slice."""
    return [
        i for i, m in enumerate(modules) if isinstance(m, GptBlock_Attn)
    ]


def draft_slice_indices(modules, draft_blocks: int) -> list:
    """Module indices of the prefix-slice draft model for speculative
    decoding: embeddings + the first ``draft_blocks`` attention units
    (with everything between them) + the LM head.

    The draft is a *layer-config slice that shares the target's
    params*: because the slice is a PREFIX of the stack, the hidden
    state entering each sliced layer is bit-identical to what the
    target computes there, so the draft's KV cache for those layers IS
    the target's — it can read and write the same slabs/pages, needs no
    prefill of its own, and costs only ``draft_blocks / num_blocks`` of
    a decode step plus one early LM-head application.  Returns the
    index list into the full module/param lists (the serving engine
    slices both with it); raises when the stack is not a decodable GPT
    or ``draft_blocks`` does not leave at least one target-only
    attention unit (a draft as deep as the target verifies nothing).
    """
    if not modules or not isinstance(modules[0], GptEmbeddings):
        raise ValueError(
            "expected a GPT stack: GptEmbeddings + GptBlock_Attn units"
        )
    attn = attn_indices(modules)
    if int(draft_blocks) < 1:
        raise ValueError(
            f"draft_blocks must be >= 1, got {draft_blocks}"
        )
    if int(draft_blocks) >= len(attn):
        raise ValueError(
            f"draft_blocks={draft_blocks} must be smaller than the "
            f"target's {len(attn)} attention units — a draft as deep "
            f"as the target cannot speed anything up"
        )
    if not isinstance(modules[-1], GptLmHead):
        raise ValueError(
            "expected the stack to end in GptLmHead (the draft reuses "
            "the target's head at the slice point)"
        )
    # everything up to AND INCLUDING the block that follows the last
    # drafted attention unit's MLP — i.e. stop just before the next
    # attention unit — then jump to the head
    cut = attn[int(draft_blocks)]
    return list(range(cut)) + [len(modules) - 1]


def apply_kv_cached(modules, params_list, data, caches, index):
    """Thread one decode step through a module SLICE.

    ``data`` is token ids [B, Lq] when the slice starts with
    :class:`GptEmbeddings`, else the hidden state handed over from the
    previous pipeline stage; ``caches`` is one (k, v) slab pair per
    attention unit in the slice (``serving/kv_cache.py`` layout);
    ``index`` is a scalar or a per-row [B] vector.  Returns (output,
    updated caches).  This is the single decode-threading implementation
    — :class:`CachedGptDecoder` runs it over the whole stack, the
    serving engine over each stage's slice.
    """
    if len(params_list) != len(modules):
        raise ValueError(
            f"got {len(params_list)} param trees for "
            f"{len(modules)} layers"
        )
    new_caches = list(caches)
    n_attn = len(attn_indices(modules))
    if len(new_caches) != n_attn:
        raise ValueError(
            f"got {len(new_caches)} cache pairs for {n_attn} "
            f"attention units"
        )
    cache_i = 0
    for module, params in zip(modules, params_list):
        if isinstance(module, GptEmbeddings):
            data = module.apply({"params": params}, data, index,
                                method=GptEmbeddings.decode)
        elif isinstance(module, GptBlock_Attn):
            k, v = new_caches[cache_i]
            data, k, v = module.apply({"params": params}, data, k, v,
                                      index, method=GptBlock_Attn.decode)
            new_caches[cache_i] = (k, v)
            cache_i += 1
        else:
            data = module.apply({"params": params}, data)
    return data, new_caches


def apply_kv_paged(
    modules, params_list, data, slabs, page_table, index, valid_len,
    attn_impl: str = "xla",
):
    """Thread one PAGED decode step through a module slice — the paged
    twin of :func:`apply_kv_cached`.

    ``slabs`` is one ``[num_pages, page_size, heads * head_dim]`` (k, v)
    pair per attention unit in the slice (plain arrays or
    ``QuantizedPages``); ``page_table``/``index``/``valid_len`` are
    shared across the slice's layers (one logical sequence per row,
    every layer caches it at the same positions); ``attn_impl``
    (``"xla"`` reference / ``"pallas"`` fused kernel) threads to every
    attention unit — see :meth:`GptBlock_Attn.decode_paged`.
    Both prefill (``Lq = bucket``, ``index`` = per-row shared-prefix
    offsets) and decode (``Lq = 1``) are this one function at different
    input shapes, so the steady state compiles exactly one decode
    program and one prefill program per bucket (and table width).
    """
    if len(params_list) != len(modules):
        raise ValueError(
            f"got {len(params_list)} param trees for "
            f"{len(modules)} layers"
        )
    new_slabs = list(slabs)
    n_attn = len(attn_indices(modules))
    if len(new_slabs) != n_attn:
        raise ValueError(
            f"got {len(new_slabs)} cache pairs for {n_attn} "
            f"attention units"
        )
    cache_i = 0
    for module, params in zip(modules, params_list):
        if isinstance(module, GptEmbeddings):
            data = module.apply({"params": params}, data, index,
                                method=GptEmbeddings.decode)
        elif isinstance(module, GptBlock_Attn):
            k, v = new_slabs[cache_i]
            data, k, v = module.apply(
                {"params": params}, data, k, v, page_table, index,
                valid_len, attn_impl, method=GptBlock_Attn.decode_paged,
            )
            new_slabs[cache_i] = (k, v)
            cache_i += 1
        else:
            data = module.apply({"params": params}, data)
    return data, new_slabs


class CachedGptDecoder:
    """KV-cache incremental decoding over the decomposed GPT layer stack.

    The reference framework has no decoding path at all; round 1 shipped a
    fixed-shape full-forward ``generate`` (O(L^2) work per token).  This
    decoder reuses the *same layer modules and param trees* as the
    ``LayerStack`` the pipeline splits, but threads a fixed-shape KV cache
    ([B, max_len, heads, head_dim] per attention unit, allocated and
    updated by ``serving/kv_cache.py`` — the one KV-cache implementation)
    in place — O(L) work per token, one compiled shape for prefill and
    one for the single-token step.
    """

    def __init__(self, stack):
        self.modules = decode_modules(stack)
        self._attn_idx = attn_indices(self.modules)
        if not self._attn_idx or not isinstance(
            self.modules[0], GptEmbeddings
        ):
            raise ValueError(
                "expected a GPT stack: GptEmbeddings + GptBlock_Attn units"
            )

    def init_cache(self, batch: int, max_len: int):
        """Zeroed fixed-shape KV caches: [(k, v)] per attention unit."""
        from ..serving.kv_cache import (
            init_layer_caches,
            kv_spec_from_config,
        )

        specs = [
            kv_spec_from_config(_gcfg(self.modules[i].config).to_dict(),
                                max_len)
            for i in self._attn_idx
        ]
        return init_layer_caches(specs, batch)

    def apply_cached(self, params_list, tokens, caches, index):
        """Forward ``tokens`` [B, Lq] at positions index..index+Lq-1.

        Returns (logits [B, Lq, V], updated caches).
        """
        return apply_kv_cached(
            self.modules, params_list, tokens, caches, index
        )


def generate_cached(
    stack,
    params_list,
    prompt,
    max_new_tokens: int,
    context_length: int,
    temperature: float = 0.0,
    rng: Optional[jax.Array] = None,
):
    """KV-cache autoregressive decoding; token-identical to ``generate``.

    One jitted program: prefill over the prompt, then ``lax.scan`` over
    single-token steps (no per-token dispatch, no O(L^2) recompute).  The
    rng split sequence mirrors ``generate`` so sampled outputs match too.
    The compiled program is cached on the stack (keyed by decode shapes),
    so repeated calls with the same shapes pay compilation once.
    """
    import numpy as np

    prompt = np.asarray(prompt)
    if prompt.ndim == 1:
        prompt = prompt[None]
    batch, start_len = prompt.shape
    if start_len + max_new_tokens > context_length:
        raise ValueError(
            f"prompt ({start_len}) + new tokens ({max_new_tokens}) exceed "
            f"context_length={context_length}"
        )
    max_pos = _gcfg(
        getattr(stack, "modules", [None])[0].config
    ).max_position_embeddings
    if context_length > max_pos:
        # inside jit the wpe gather would silently clamp, not error —
        # mirror generate()'s loud failure on the padded full forward
        raise ValueError(
            f"context_length={context_length} exceeds "
            f"max_position_embeddings={max_pos}"
        )
    if max_new_tokens == 0:
        return prompt.astype(np.int32)
    if rng is None:
        rng = jax.random.key(0)  # unused when greedy; keeps one jit shape

    # decoder + compiled programs live on the stack so their lifetime (and
    # the jit cache's) matches the model's, not one call
    cache_dict = getattr(stack, "_decode_programs", None)
    if cache_dict is None:
        cache_dict = stack._decode_programs = {}
    decoder = cache_dict.get("decoder")
    if decoder is None:
        decoder = cache_dict["decoder"] = CachedGptDecoder(stack)
    key = (batch, start_len, max_new_tokens, context_length,
           temperature if temperature > 0.0 else 0.0)
    run_jit = cache_dict.get(key)
    if run_jit is None:

        def sample(logits, rng):
            if temperature <= 0.0:
                return logits.argmax(axis=-1).astype(jnp.int32), rng
            rng, sub = jax.random.split(rng)
            return (
                jax.random.categorical(
                    sub, logits.astype(jnp.float32) / temperature, axis=-1
                ).astype(jnp.int32),
                rng,
            )

        def run(params_list, prompt_ids, caches, rng):
            logits, caches = decoder.apply_cached(params_list, prompt_ids,
                                                  caches, 0)
            first, rng = sample(logits[:, -1], rng)

            def step(carry, _):
                tok, caches, rng, index = carry
                logits, caches = decoder.apply_cached(
                    params_list, tok[:, None], caches, index
                )
                nxt, rng = sample(logits[:, 0], rng)
                return (nxt, caches, rng, index + 1), nxt

            (_, _, _, _), rest = jax.lax.scan(
                step, (first, caches, rng, jnp.int32(start_len)),
                None, length=max_new_tokens - 1,
            )
            return jnp.concatenate(
                [first[:, None], jnp.moveaxis(rest, 0, 1)], axis=1
            )

        run_jit = cache_dict[key] = jax.jit(run)

    caches = decoder.init_cache(batch, context_length)
    new_tokens = run_jit(params_list, jnp.asarray(prompt, jnp.int32),
                         caches, rng)
    return np.concatenate([prompt, np.asarray(new_tokens)], axis=1)


__all__ = [
    "GptConfig",
    "GptEmbeddings",
    "GptBlock_Attn",
    "GptBlock_Mlp",
    "GptBlock_MoeMlp",
    "GptLmHead",
    "gpt_layer_configs",
    "causal_lm_loss",
    "generate",
    "generate_cached",
    "CachedGptDecoder",
    "apply_kv_cached",
    "apply_kv_paged",
    "attn_indices",
    "decode_modules",
    "draft_slice_indices",
]
