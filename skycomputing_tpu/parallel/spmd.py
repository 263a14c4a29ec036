"""Compiled SPMD pipeline: GPipe over a ('pp',) mesh in ONE jitted program.

This is the homogeneous-cluster fast path, complementary to the host-driven
MPMD engine in :mod:`.pipeline`:

- the MPMD engine supports *unequal* stages (the allocator's whole point)
  and re-slices without recompiling unmoved stages;
- this SPMD engine requires uniform stages but compiles the ENTIRE training
  step — forward, pipelined microbatch schedule, backward, optimizer — into
  a single XLA program over a ``jax.sharding.Mesh``, with stage-to-stage
  activation handoff as ``lax.ppermute`` over ICI neighbor links and
  per-stage parameters sharded on the ``pp`` mesh axis (leading-axis stack).

The schedule is classic GPipe fill-drain: with S stages and M microbatches
the shard_map body scans T = M + S - 1 ticks; at tick t, stage s computes
microbatch ``t - s`` (bubble ticks compute-and-discard).  Backward is just
``jax.grad`` through the scan — ppermute transposes to the reverse
permutation, so XLA derives the reverse schedule automatically; no
distributed autograd machinery exists anywhere (the reference needed
torch.distributed.autograd + DistributedOptimizer for this,
``scaelum/runner/runner.py:127-139``).

Non-repeated ends (embeddings / pooler / classifier) run replicated outside
the pipelined block.  Dropout is disabled in this path (deterministic
pipeline body); the MPMD engine handles stochastic training.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import flax.linen as nn

from ..models.bert import (
    ACT2FN,
    BertEmbeddings,
    BertLayer_Body,
    BertLayer_Head,
    BertLayer_Tail,
    BertPooler,
    BertTailForClassification,
)
from ..models.bert_config import BertConfig


class EncoderUnit(nn.Module):
    """One full encoder trio (attention + FFN)."""

    config: Any
    deterministic: bool = True

    @nn.compact
    def __call__(self, hidden, mask):
        hidden, mask = BertLayer_Head(
            self.config, self.deterministic, name="head"
        )(hidden, mask)
        inter, attn, mask = BertLayer_Body(
            self.config, self.deterministic, name="body"
        )(hidden, mask)
        hidden, mask = BertLayer_Tail(
            self.config, self.deterministic, name="tail"
        )(inter, attn, mask)
        return hidden, mask


class EncoderStage(nn.Module):
    """``units`` encoder trios = one uniform pipeline stage.

    Each unit is rematerialized: through the GPipe scan the backward pass
    otherwise stores every tick's intermediate activations (attention
    scores context, FFN up-projection); with remat only each unit's input
    survives to the backward, bounding per-tick residency at one hidden
    block per unit.
    """

    config: Any
    units: int
    deterministic: bool = True

    @nn.compact
    def __call__(self, hidden, mask):
        for u in range(self.units):
            hidden, mask = nn.remat(EncoderUnit)(
                self.config, self.deterministic, name=f"unit_{u}"
            )(hidden, mask)
        return hidden, mask


class _TpDense(nn.Module):
    """Tensor-parallel dense holding this device's weight shard.

    ``col``: output features sharded over the tp axis (no collective);
    ``row``: input features sharded, partial products ``psum``-reduced over
    the tp axis before the (replicated) bias is added.  The param tree keeps
    the plain Dense layout (``kernel``/``bias``) so full weights split into
    tp shards by pure reshape/transpose (see ``split_stage_params_for_tp``).
    """

    out_features: int
    dtype: Any
    mode: str  # 'col' | 'row'
    axis_name: str = "tp"

    @nn.compact
    def __call__(self, x):
        kernel = self.param(
            "kernel", nn.initializers.zeros,
            (x.shape[-1], self.out_features), jnp.float32,
        )
        bias = self.param(
            "bias", nn.initializers.zeros, (self.out_features,), jnp.float32
        )
        y = x @ kernel.astype(self.dtype)
        if self.mode == "row":
            y = lax.psum(y, self.axis_name)
        return y + bias.astype(self.dtype)


class TpEncoderUnit(nn.Module):
    """Megatron-style tensor-parallel encoder trio for the pipeline body.

    Attention q/k/v are column-parallel (heads split across tp), the
    attention output projection and the FFN down-projection are
    row-parallel with a ``psum``; LayerNorms and residuals are replicated.
    Param tree mirrors :class:`EncoderUnit` (``head/self/query`` etc.) with
    tp-local leaf shapes.

    Dropout (``deterministic=False``) follows Megatron RNG discipline: the
    dropouts on REPLICATED activations (attention output, FFN output —
    both after the row-parallel psum) draw from the shared per-tick key,
    so every tp rank applies the identical mask and replicas stay equal;
    the attention-probs dropout acts on head-SHARDED activations and is
    desynchronized across tp by folding ``lax.axis_index('tp')`` into its
    key (independent masks per head shard).
    """

    config: Any
    tp: int
    axis_name: str = "tp"
    deterministic: bool = True

    @nn.compact
    def __call__(self, hidden, mask):
        cfg = BertConfig.from_dict(self.config)
        dtype = jnp.dtype(cfg.dtype)
        if (
            cfg.hidden_size % self.tp
            or cfg.num_attention_heads % self.tp
            or cfg.intermediate_size % self.tp
        ):
            raise ValueError(
                f"hidden/heads/intermediate "
                f"({cfg.hidden_size}/{cfg.num_attention_heads}/"
                f"{cfg.intermediate_size}) must all be divisible by "
                f"tp={self.tp}"
            )
        n_heads = cfg.num_attention_heads // self.tp
        head_dim = cfg.hidden_size // cfg.num_attention_heads
        h_local = cfg.hidden_size // self.tp
        i_local = cfg.intermediate_size // self.tp
        deterministic = self.deterministic
        tp_axis = self.axis_name

        class Head(nn.Module):
            @nn.compact
            def __call__(sf, hidden, mask):
                class Self(nn.Module):
                    @nn.compact
                    def __call__(sf2, x, mask):
                        mk = lambda nm: _TpDense(
                            h_local, dtype, "col", tp_axis, name=nm
                        )
                        split = lambda t: t.reshape(
                            t.shape[0], t.shape[1], n_heads, head_dim
                        )
                        q = split(mk("query")(x))
                        k = split(mk("key")(x))
                        v = split(mk("value")(x))
                        scores = jnp.einsum("blhd,bmhd->bhlm", q, k) / (
                            jnp.sqrt(jnp.asarray(head_dim, dtype))
                        )
                        scores = scores + mask
                        probs = jax.nn.softmax(
                            scores.astype(jnp.float32), axis=-1
                        ).astype(dtype)
                        if (
                            not deterministic
                            and cfg.attention_probs_dropout_prob > 0.0
                        ):
                            # head-sharded region: desync masks across tp
                            rng = jax.random.fold_in(
                                sf2.make_rng("dropout"),
                                lax.axis_index(tp_axis),
                            )
                            probs = nn.Dropout(
                                cfg.attention_probs_dropout_prob
                            )(probs, deterministic=False, rng=rng)
                        ctx = jnp.einsum("bhlm,bmhd->blhd", probs, v)
                        return ctx.reshape(ctx.shape[0], ctx.shape[1],
                                           h_local)

                class Out(nn.Module):
                    @nn.compact
                    def __call__(sf2, ctx, residual):
                        y = _TpDense(cfg.hidden_size, dtype, "row",
                                     tp_axis, name="dense")(ctx)
                        # replicated region (post-psum): shared key ->
                        # identical mask on every tp rank
                        y = nn.Dropout(cfg.hidden_dropout_prob)(
                            y, deterministic=deterministic
                        )
                        out = nn.LayerNorm(
                            epsilon=1e-12, dtype=jnp.float32,
                            name="LayerNorm",
                        )(y + residual)
                        return out.astype(dtype)

                ctx = Self(name="self")(hidden, mask)
                return Out(name="output")(ctx, hidden), mask

        class Body(nn.Module):
            @nn.compact
            def __call__(sf, attn_out, mask):
                act = ACT2FN[cfg.hidden_act]
                inter = act(_TpDense(i_local, dtype, "col", tp_axis,
                                     name="dense_act")(attn_out))
                return inter, attn_out, mask

        class Tail(nn.Module):
            @nn.compact
            def __call__(sf, inter, attn_out, mask):
                y = _TpDense(cfg.hidden_size, dtype, "row", tp_axis,
                             name="dense")(inter)
                y = nn.Dropout(cfg.hidden_dropout_prob)(
                    y, deterministic=deterministic
                )
                out = nn.LayerNorm(
                    epsilon=1e-12, dtype=jnp.float32, name="LayerNorm"
                )(y + attn_out)
                return out.astype(dtype), mask

        hidden, mask = Head(name="head")(hidden, mask)
        inter, attn, mask = Body(name="body")(hidden, mask)
        return Tail(name="tail")(inter, attn, mask)


class TpEncoderStage(nn.Module):
    """``units`` tensor-parallel encoder trios; remat like EncoderStage."""

    config: Any
    units: int
    tp: int
    axis_name: str = "tp"
    deterministic: bool = True

    @nn.compact
    def __call__(self, hidden, mask):
        for u in range(self.units):
            hidden, mask = nn.remat(TpEncoderUnit)(
                self.config, self.tp, self.axis_name, self.deterministic,
                name=f"unit_{u}",
            )(hidden, mask)
        return hidden, mask


def _leaf_role(path) -> Tuple[str, str]:
    keys = [getattr(p, "key", str(p)) for p in path]
    return keys[-2], keys[-1]  # (module, param) e.g. ('query', 'kernel')


# module names whose Dense is column- vs row-parallel, per model family
BERT_TP_COL = ("query", "key", "value", "dense_act")
BERT_TP_ROW = ("dense",)


def _tp_split_axis(module, param, col_modules, row_modules):
    """Which axis of a full leaf splits across tp; None = replicate.

    Role sets may name a whole submodule (every param of a Dense) or a
    specific ``(module, param)`` pair (direct params, e.g. MoE expert
    tensors).  Column-parallel leaves split their LAST axis (output
    features / expert up-projection); row-parallel ones split the
    second-to-last (input features), with module-matched row biases
    replicated (they are added after the psum).
    """
    if module in col_modules or (module, param) in col_modules:
        return -1
    if (module, param) in row_modules:
        return -2
    if module in row_modules and param == "kernel":
        return -2
    return None


def split_stage_params_for_tp(stages, tp: int,
                              col_modules=BERT_TP_COL,
                              row_modules=BERT_TP_ROW):
    """[P, ...full...] stacked stage params -> [P, tp, ...local...].

    Column-parallel leaves (q/k/v, FFN up) slice output features; row-
    parallel kernels (attention out, FFN down) slice input features; biases
    of row-parallel layers and LayerNorms replicate across tp.
    ``col_modules``/``row_modules`` name the submodules (or
    ``(module, param)`` pairs) playing each role — defaults match the BERT
    encoder; the GPT engines pass their own.
    """

    def split(path, leaf):
        module, param = _leaf_role(path)
        ax = _tp_split_axis(module, param, col_modules, row_modules)
        if ax is None:
            # row-parallel bias, LayerNorm scale/bias, routers: replicate
            return jnp.broadcast_to(
                leaf[:, None], (leaf.shape[0], tp) + leaf.shape[1:]
            )
        k = ax % leaf.ndim
        shape = leaf.shape
        parts = leaf.reshape(
            shape[:k] + (tp, shape[k] // tp) + shape[k + 1:]
        )
        return jnp.moveaxis(parts, k, 1)

    return jax.tree_util.tree_map_with_path(split, stages)


@jax.custom_vjp
def _psum_grad_tp(x):
    """Identity whose cotangent is ``psum``-med over the 'tp' axis.

    Replicated param leaves (LayerNorms, row-parallel biases) get their
    copies stacked on a tp axis of the global array, so the spec-driven
    shard_map transpose hands each device only its *partial* cotangent
    (the partials sum to the true one; sharded kernels are exact because
    their reverse path crosses the forward ``psum``, whose transpose is a
    ``psum`` under ``check_vma=False``).  Wrapping the forward use of a
    replicated leaf in this identity makes each copy's gradient the full
    cross-tp sum, keeping copies equal and equal to the unsharded model's
    gradient.
    """
    return x


def _psum_grad_tp_fwd(x):
    return x, None


def _psum_grad_tp_bwd(_, g):
    return (lax.psum(g, "tp"),)


_psum_grad_tp.defvjp(_psum_grad_tp_fwd, _psum_grad_tp_bwd)


def merge_stage_params_from_tp(stages_tp,
                               col_modules=BERT_TP_COL,
                               row_modules=BERT_TP_ROW):
    """Inverse of :func:`split_stage_params_for_tp`."""

    def merge(path, leaf):
        module, param = _leaf_role(path)
        ax = _tp_split_axis(module, param, col_modules, row_modules)
        if ax is None:
            return leaf[:, 0]
        # leaf: [P, tp, ...local...]; put tp back next to its split axis
        k = ax % (leaf.ndim - 1)  # axis index in the FULL (merged) leaf
        parts = jnp.moveaxis(leaf, 1, k)
        shape = parts.shape
        return parts.reshape(
            shape[:k] + (shape[k] * shape[k + 1],) + shape[k + 2:]
        )

    return jax.tree_util.tree_map_with_path(merge, stages_tp)


class CompiledBertPipeline:
    """BERT classifier with the encoder pipelined across a ('pp',) mesh."""

    # Dense submodule names by Megatron role (overridden per model family);
    # used both to split full weights into tp shards and to pick which
    # leaves need the replicated-gradient guard in the stage body
    tp_col_modules = BERT_TP_COL
    tp_row_modules = BERT_TP_ROW

    def __init__(
        self,
        config: Any,
        mesh: Mesh,
        units_per_stage: int,
        num_classes: int = 3,
        num_microbatches: Optional[int] = None,
        learning_rate: float = 1e-3,
        virtual_stages: int = 1,
        optimizer: Optional[optax.GradientTransformation] = None,
        zero1: bool = False,
        zero2: bool = False,
        zero3: bool = False,
        deterministic: bool = True,
    ):
        # deterministic=False enables dropout end to end (the reference
        # fine-tunes with dropout throughout,
        # scaelum/model/bert_layers.py): replicated ends use plain flax
        # rngs, the pipelined body threads a threefry key through the ring
        # scan folded by (device, tick) — every (stage, tick, microbatch)
        # cell draws an independent mask, reproducible per seed.
        self.deterministic = bool(deterministic)
        self.cfg = self._parse_config(config)
        self.mesh = mesh
        self.num_stages = int(mesh.shape["pp"])
        # interleaved scheduling (Megatron-style): each device owns
        # ``virtual_stages`` model chunks placed round-robin.  At M == S the
        # per-device bubble shrinks from (S-1)/(M+S-1) to (S-1)/(M+V*S-1)
        # in chunk-time units; for M < S idle ticks are V*(S-M)+M-1.  The
        # collision-free wavefront needs M <= S.
        self.virtual_stages = int(virtual_stages)
        if self.virtual_stages < 1:
            raise ValueError(
                f"virtual_stages must be >= 1, got {virtual_stages}"
            )
        # optional data-parallel axis: batch sharded over 'dp', stage params
        # replicated across it.  Inside the shard_map the stage-grad
        # reduction over 'dp' comes from the spec-driven transpose (params'
        # in_spec P('pp') omits 'dp', so the cotangent is psummed over it);
        # GSPMD handles only the code outside the shard_map.
        self.dp = int(mesh.shape["dp"]) if "dp" in mesh.shape else 1
        # optional tensor-parallel axis: each stage's weights sharded
        # Megatron-style over 'tp' with explicit psums in the stage body
        self.tp = int(mesh.shape["tp"]) if "tp" in mesh.shape else 1
        self.units_per_stage = units_per_stage
        self.num_classes = num_classes
        # interleaved scheduling accepts any M: the collision-free
        # wavefront covers M <= S, the grouped Megatron schedule covers
        # S | M, and other M pad up to the next multiple of S (pads are
        # sliced away; see _interleaved_encoder)
        self.num_microbatches = num_microbatches or self.num_stages
        self.optimizer = optimizer or optax.sgd(learning_rate)
        # ZeRO-1: shard optimizer-state tensors (momenta etc.) over the dp
        # axis instead of replicating them.  Under jit this is nothing but
        # sharding annotations — XLA derives the reduce-scatter of grads
        # into state shards and the all-gather of updates by itself.
        self.zero1 = bool(zero1)
        if self.zero1 and self.dp == 1:
            raise ValueError("zero1 requires a 'dp' mesh axis of size > 1")
        # ZeRO-2: additionally pin the GRADIENT tree to the same dp shards
        # (with_sharding_constraint right at the value_and_grad output), so
        # the full-size replicated gradient buffer never materializes —
        # XLA reduce-scatters the cross-dp gradient sum straight into
        # shards and every downstream optimizer op stays sharded.
        self.zero2 = bool(zero2)
        if self.zero2 and not self.zero1:
            raise ValueError("zero2 extends zero1; pass zero1=True as well")
        # ZeRO-3 / FSDP: stage params live dp-SHARDED at rest (one weight
        # axis split over 'dp' on top of the 'pp'/'tp' stacking) and are
        # all-gathered inside the stage body right before use; the
        # gather's transpose is a reduce-scatter, so gradients come out
        # dp-sharded too and the optimizer update runs entirely on
        # shards.  Param/state/grad memory all divide by dp.
        self.zero3 = bool(zero3)
        if self.zero3 and self.dp == 1:
            raise ValueError("zero3 requires a 'dp' mesh axis of size > 1")
        self._zero3_axes = None  # per-leaf gather axis, built by init()
        self._stage_in_specs = None  # per-leaf specs (zero3), ditto

        self._build_modules(units_per_stage, num_classes)

        self._stage_spec = P("pp", "tp") if self.tp > 1 else P("pp")
        self._repl_spec = P()
        self.opt_shardings = None
        self.param_shardings: Optional[Dict] = None
        self._train_step = None

    @staticmethod
    def _parse_config(config):
        return BertConfig.from_dict(config)

    def _build_modules(self, units_per_stage: int, num_classes: int) -> None:
        """Model-specific module construction (overridden per family)."""
        cfg_dict = self.cfg.to_dict()
        det = self.deterministic
        self.embeddings = BertEmbeddings(cfg_dict, deterministic=det)
        self.stage = EncoderStage(cfg_dict, units_per_stage,
                                  deterministic=det)
        self.tp_stage = (
            TpEncoderStage(cfg_dict, units_per_stage, self.tp,
                           deterministic=det)
            if self.tp > 1 else None
        )
        self.pooler = BertPooler(cfg_dict, deterministic=det)
        self.classifier = BertTailForClassification(
            hidden_dropout_prob=self.cfg.hidden_dropout_prob,
            hidden_size=self.cfg.hidden_size,
            num_classes=num_classes,
            deterministic=det,
            dtype=self.cfg.dtype,
        )

    def _pick_dp_axis(self, shape, first_axis: int) -> int:
        """Last dp-divisible axis of ``shape`` at/after ``first_axis``.

        The ONE rule shared by ZeRO state sharding (`_zero1_sharding`),
        ZeRO-2 gradient pinning, and ZeRO-3 param sharding — all three
        must agree or XLA reshards every stage gradient each step.
        Returns -1 when no axis qualifies.
        """
        for ax in range(len(shape) - 1, first_axis - 1, -1):
            if shape[ax] % self.dp == 0 and shape[ax] >= self.dp:
                return ax
        return -1

    def _stage_shardings(self, stages):
        """Per-leaf shardings for the stacked stage tree.

        Without zero3 every leaf gets the uniform ``self._stage_spec``;
        with zero3 one dp-divisible weight axis per leaf additionally
        carries 'dp', and the per-leaf gather axis (post-extraction
        coordinates, -1 = replicated) is recorded for the stage body.
        """
        stage_dims = 2 if self.tp > 1 else 1

        class _SpecAx:  # opaque pair so tree_map treats it as a leaf
            def __init__(self, spec, ax):
                self.spec, self.ax = spec, ax

        def spec_and_axis(leaf):
            shape = np.shape(leaf)
            spec = list(self._stage_spec) + [None] * (len(shape) - stage_dims)
            ax = self._pick_dp_axis(shape, stage_dims) if self.zero3 else -1
            if ax >= 0:
                spec[ax] = "dp"
            return _SpecAx(P(*spec), ax - stage_dims if ax >= 0 else -1)

        pairs = jax.tree_util.tree_map(spec_and_axis, stages)
        specs = jax.tree_util.tree_map(lambda p: p.spec, pairs)
        self._zero3_axes = jax.tree_util.tree_map(lambda p: p.ax, pairs)
        self._stage_in_specs = specs if self.zero3 else self._stage_spec
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s), specs
        )

    def _gather_zero3(self, params):
        """all-gather dp-sharded leaves inside the stage body (zero3)."""
        if not self.zero3:
            return params
        return jax.tree_util.tree_map(
            lambda x, ax: (
                lax.all_gather(x, "dp", axis=ax, tiled=True) if ax >= 0
                else x
            ),
            params, self._zero3_axes,
        )

    # --- init ----------------------------------------------------------------
    def init(self, rng: jax.Array, input_ids, token_type_ids, attention_mask):
        """Initialize params: stage params stacked on a leading 'pp' axis."""
        k_embed, k_stage, k_pool, k_cls = jax.random.split(rng, 4)
        # stochastic modules consume a 'dropout' stream during their init
        # forward; masks don't create params, so the tree is identical to
        # the deterministic engine's
        drop = (
            {} if self.deterministic
            else {"dropout": jax.random.fold_in(rng, 99)}
        )
        embed_vars = self.embeddings.init(
            {"params": k_embed, **drop},
            input_ids, token_type_ids, attention_mask,
        )
        hidden, mask4 = self.embeddings.apply(
            embed_vars, input_ids, token_type_ids, attention_mask,
            rngs=drop or None,
        )

        def init_one_stage(key):
            return self.stage.init(
                {"params": key, **drop}, hidden, mask4
            )["params"]

        S, V = self.num_stages, self.virtual_stages
        chunk_keys = jax.random.split(k_stage, S * V)
        # stacked position p on device p//V, local slot p%V, holds model
        # chunk c = (p%V)*S + p//V — round-robin placement so sharding the
        # leading axis over 'pp' gives each device chunks {d, S+d, 2S+d,...}
        order = [(p % V) * S + p // V for p in range(S * V)]
        stages = jax.vmap(init_one_stage)(chunk_keys[jnp.asarray(order)])
        if self.tp > 1:
            # full weights -> per-device Megatron shards on a new axis 1
            stages = split_stage_params_for_tp(
                stages, self.tp, self.tp_col_modules, self.tp_row_modules
            )

        pooler_vars = self.pooler.init(
            {"params": k_pool, **drop}, hidden, mask4
        )
        pooled = self.pooler.apply(pooler_vars, hidden, mask4,
                                   rngs=drop or None)
        cls_vars = self.classifier.init({"params": k_cls, **drop}, pooled)

        params = {
            "embeddings": embed_vars["params"],
            "stages": stages,
            "pooler": pooler_vars["params"],
            "classifier": cls_vars["params"],
        }
        self.param_shardings = {
            "embeddings": NamedSharding(self.mesh, self._repl_spec),
            "stages": self._stage_shardings(stages),
            "pooler": NamedSharding(self.mesh, self._repl_spec),
            "classifier": NamedSharding(self.mesh, self._repl_spec),
        }
        params = jax.device_put(params, self.param_shardings)
        return params

    def init_opt_state(self, params):
        # any momentum/trace buffers are shaped like params and inherit
        # their shardings (params are already placed by init())
        opt_state = self.optimizer.init(params)
        if not self.zero1:
            return opt_state
        self.opt_shardings = jax.tree_util.tree_map(
            self._zero1_sharding, opt_state
        )
        return jax.device_put(opt_state, self.opt_shardings)

    def _zero1_sharding(self, leaf):
        """dp-shard the largest dp-divisible axis of a state tensor.

        Param-shaped leaves keep their stage ('pp'/'tp') dims on the
        leading axes and additionally split one weight axis over 'dp';
        scalars/counters stay replicated.
        """
        shape = np.shape(leaf)
        if len(shape) == 0:
            return NamedSharding(self.mesh, P())
        # leading axes belong to the stacked-stage layout when they match
        stage_axes = 0
        if shape[0] == self.num_stages * self.virtual_stages:
            stage_axes = 2 if self.tp > 1 and len(shape) > 1 and (
                shape[1] == self.tp
            ) else 1
        spec = (["pp", "tp"][: stage_axes] + [None] * (len(shape) - stage_axes))
        best = self._pick_dp_axis(shape, stage_axes)
        if best >= 0:
            spec[best] = "dp"
        elif stage_axes == 0:
            return NamedSharding(self.mesh, P())  # replicated (embeddings
            # and heads are small next to the encoder stack)
        return NamedSharding(self.mesh, P(*spec))

    # side_outputs=True (set by engines whose stages accumulate a scalar
    # into the ring's side tensor, e.g. MoE aux loss): the schedule returns
    # (hidden_out, side_out) instead of hidden_out alone
    side_outputs = False

    # --- the pipelined encoder ----------------------------------------------
    def _run_ring_schedule(self, body, stage_params, hidden_mb, mask_mb,
                           rng=None):
        """Shared shard_map scaffolding for both pipeline schedules.

        ``body(local_stage_params, hidden_mb, mask_mb[, rng_data]) ->
        [M, ...]`` runs per device; activations keep their optional dp
        sharding, outputs stack per-stage buffers along axis 0 and only
        the last device's block (the final stage/chunk) is meaningful.
        With ``side_outputs`` the body returns a (hidden, side) buffer
        pair.  M comes from the input's leading axis (the padded count
        when the grouped schedule padded up to a multiple of S).

        ``rng`` (a jax PRNG key; stochastic engines only) enters the body
        as replicated raw key data — every device derives its own stream
        by folding in its mesh position, so no per-device key plumbing is
        needed at the call site.
        """
        M = hidden_mb.shape[0]
        act_spec = P(None, "dp") if self.dp > 1 else P()
        out_spec = P("pp", "dp") if self.dp > 1 else P("pp")
        out_specs = (out_spec, out_spec) if self.side_outputs else out_spec
        stage_specs = (
            self._stage_in_specs if self._stage_in_specs is not None
            else self._stage_spec
        )
        in_specs = [stage_specs, act_spec, act_spec]
        args = [stage_params, hidden_mb, mask_mb]
        if rng is not None:
            in_specs.append(P())
            args.append(jax.random.key_data(rng))
        out = jax.shard_map(
            body,
            mesh=self.mesh,
            in_specs=tuple(in_specs),
            out_specs=out_specs,
            check_vma=False,
        )(*args)
        if self.side_outputs:
            return out[0][-M:], out[1][-M:]
        return out[-M:]

    def _stage_rng_stream(self, maybe_rng):
        """Per-device dropout-key base + per-tick rngs-dict factory.

        ``maybe_rng`` is the body's trailing varargs: empty for the
        deterministic engine, else one raw-key-data array.  The base key
        folds in the device's 'pp' position; each tick t folds again, so
        every (device, tick) cell — hence every (stage/chunk, microbatch)
        pair — draws an independent, reproducible mask.
        """
        if not maybe_rng:
            return lambda t: {}
        base = jax.random.fold_in(
            jax.random.wrap_key_data(maybe_rng[0]), lax.axis_index("pp")
        )
        if self.dp > 1:
            # data-parallel shards hold different rows; desync their masks
            # (tp deliberately NOT folded — replicated-region masks must
            # match across tp, see TpEncoderUnit)
            base = jax.random.fold_in(base, lax.axis_index("dp"))
        return lambda t: {
            "rngs": {"dropout": jax.random.fold_in(base, t)}
        }

    def _guard_tp_replicated(self, local_stage_params):
        """Wrap tp-replicated leaves so their gradient sums across tp."""
        if self.tp == 1:
            return local_stage_params
        col, row = self.tp_col_modules, self.tp_row_modules

        def guard(path, leaf):
            module, param = _leaf_role(path)
            if _tp_split_axis(module, param, col, row) is not None:
                return leaf  # genuinely sharded: transpose is exact
            return _psum_grad_tp(leaf)

        return jax.tree_util.tree_map_with_path(guard, local_stage_params)

    def _select_chunk_params(self, local_stage_params, k_c):
        """This device's chunk ``k_c`` from its [V, (tp,) ...] local leaves.

        With zero3 the selected chunk is all-gathered over dp HERE, per
        tick — FSDP-style streaming: only the chunk in use is ever
        materialized full-size, the rest stay sharded at rest.
        """
        tp = self.tp

        def index_chunk(x):
            x = lax.dynamic_index_in_dim(x, k_c, 0, keepdims=False)
            return x[0] if tp > 1 else x

        return self._gather_zero3(
            jax.tree_util.tree_map(index_chunk, local_stage_params)
        )

    def _pipelined_encoder(self, stage_params, hidden_mb, mask_mb,
                           rng=None):
        """shard_map GPipe: [M, mb, L, H] -> [M, mb, L, H]."""
        S = self.num_stages
        M = hidden_mb.shape[0]
        tp = self.tp
        stage_mod = self.tp_stage if tp > 1 else self.stage

        def body(local_stage_params, hidden_mb, mask_mb, *maybe_rng):
            # local leaves have leading dim 1 (this device's stage); with
            # tensor parallelism a second singleton tp-shard dim follows
            params = jax.tree_util.tree_map(
                (lambda x: x[0, 0]) if tp > 1 else (lambda x: x[0]),
                local_stage_params,
            )
            params = self._gather_zero3(params)
            params = self._guard_tp_replicated(params)
            idx = lax.axis_index("pp")
            fwd_perm = [(i, (i + 1) % S) for i in range(S)]
            tick_rngs = self._stage_rng_stream(maybe_rng)

            if self.side_outputs:
                # the side is a per-microbatch accumulator (e.g. MoE aux
                # loss): it travels WITH the microbatch around the ring
                # instead of being re-fed per stage like the BERT mask
                state = (jnp.zeros_like(hidden_mb[0]),
                         jnp.zeros_like(mask_mb[0]))
                outputs = (jnp.zeros_like(hidden_mb),
                           jnp.zeros_like(mask_mb))

                def tick_side(carry, t):
                    state, (out_h, out_s) = carry
                    recv_h, recv_s = lax.ppermute(state, "pp", fwd_perm)
                    feed = jnp.clip(t, 0, M - 1)
                    inp_h = jnp.where(idx == 0, hidden_mb[feed], recv_h)
                    inp_s = jnp.where(idx == 0, mask_mb[feed], recv_s)
                    h, s = stage_mod.apply(
                        {"params": params}, inp_h, inp_s, **tick_rngs(t)
                    )
                    w = jnp.clip(t - (S - 1), 0, M - 1)
                    out_h = lax.dynamic_update_index_in_dim(out_h, h, w, 0)
                    out_s = lax.dynamic_update_index_in_dim(out_s, s, w, 0)
                    return ((h, s), (out_h, out_s)), None

                (_, outputs), _ = lax.scan(
                    tick_side, (state, outputs), jnp.arange(M + S - 1)
                )
                return outputs

            state = jnp.zeros_like(hidden_mb[0])
            outputs = jnp.zeros_like(hidden_mb)

            def tick(carry, t):
                state, outputs = carry
                recv = lax.ppermute(state, "pp", fwd_perm)
                feed = hidden_mb[jnp.clip(t, 0, M - 1)]
                inp = jnp.where(idx == 0, feed, recv)
                mb_idx = jnp.clip(t - idx, 0, M - 1)
                out, _ = stage_mod.apply(
                    {"params": params}, inp, mask_mb[mb_idx],
                    **tick_rngs(t),
                )
                # last stage records its finished microbatch; earlier
                # (bubble) writes land on index 0 and are overwritten at
                # t == S-1 by the real microbatch 0
                w = jnp.clip(t - (S - 1), 0, M - 1)
                outputs = lax.dynamic_update_index_in_dim(
                    outputs, out, w, axis=0
                )
                return (out, outputs), None

            (_, outputs), _ = lax.scan(
                tick, (state, outputs), jnp.arange(M + S - 1)
            )
            return outputs

        return self._run_ring_schedule(body, stage_params, hidden_mb,
                                       mask_mb, rng=rng)

    def _interleaved_encoder(self, stage_params, hidden_mb, mask_mb,
                             rng=None):
        """V>1 chunk-wavefront schedule: [M, mb, L, H] -> [M, mb, L, H].

        Chunk c (device c mod S, local slot c // S) processes microbatch m
        at tick t = m + c; with M <= S each device runs at most one chunk
        per tick, and the uniform neighbor ring delivers every chunk
        transition — including slot boundaries (chunk vS-1 on device S-1
        feeds chunk vS on device 0).  For M > S (M a multiple of S) the
        grouped variant below runs instead.
        """
        S = self.num_stages
        M = hidden_mb.shape[0]
        if M > S:
            if M % S:
                # pad with zero microbatches up to a multiple of S so the
                # grouped wavefront applies; the pads ride the ring as
                # extra bubble and their outputs are sliced away.  Cost:
                # pad/M extra chunk-compute — still ahead of falling back
                # to plain GPipe when V amortizes the bubble.
                pad = S - M % S
                zeros = lambda t: jnp.concatenate(
                    [t, jnp.zeros((pad,) + t.shape[1:], t.dtype)], axis=0
                )
                out = self._interleaved_grouped_encoder(
                    stage_params, zeros(hidden_mb), zeros(mask_mb), rng=rng
                )
                if self.side_outputs:
                    return out[0][:M], out[1][:M]
                return out[:M]
            return self._interleaved_grouped_encoder(
                stage_params, hidden_mb, mask_mb, rng=rng
            )
        V = self.virtual_stages
        C = S * V
        T = M + C - 1
        tp = self.tp
        stage_mod = self.tp_stage if tp > 1 else self.stage

        def body(local_stage_params, hidden_mb, mask_mb, *maybe_rng):
            local_stage_params = self._guard_tp_replicated(local_stage_params)
            d = lax.axis_index("pp")
            fwd_perm = [(i, (i + 1) % S) for i in range(S)]
            tick_rngs = self._stage_rng_stream(maybe_rng)

            def tick_coords(t):
                """t -> (chunk slot k_c, microbatch m_c, write index w)."""
                k = (t - d) // S  # jnp floor-division: negative -> k < 0
                m = t - d - S * k
                k_c = jnp.clip(k, 0, V - 1)
                m_c = jnp.clip(m, 0, M - 1)
                w = jnp.clip(t - (C - 1), 0, M - 1)
                return k_c, m_c, w

            if self.side_outputs:
                # the side travels WITH the microbatch between chunks
                # (aux accumulator), so it rides the ring alongside hidden
                state = (jnp.zeros_like(hidden_mb[0]),
                         jnp.zeros_like(mask_mb[0]))
                outputs = (jnp.zeros_like(hidden_mb),
                           jnp.zeros_like(mask_mb))

                def tick_side(carry, t):
                    state, (out_h, out_s) = carry
                    recv_h, recv_s = lax.ppermute(state, "pp", fwd_perm)
                    k_c, m_c, w = tick_coords(t)
                    params_k = self._select_chunk_params(
                        local_stage_params, k_c
                    )
                    first = (d == 0) & (k_c == 0)
                    inp_h = jnp.where(first, hidden_mb[m_c], recv_h)
                    inp_s = jnp.where(first, mask_mb[m_c], recv_s)
                    h, s = stage_mod.apply(
                        {"params": params_k}, inp_h, inp_s, **tick_rngs(t)
                    )
                    out_h = lax.dynamic_update_index_in_dim(out_h, h, w, 0)
                    out_s = lax.dynamic_update_index_in_dim(out_s, s, w, 0)
                    return ((h, s), (out_h, out_s)), None

                (_, outputs), _ = lax.scan(
                    tick_side, (state, outputs), jnp.arange(T)
                )
                return outputs

            state = jnp.zeros_like(hidden_mb[0])
            outputs = jnp.zeros_like(hidden_mb)

            def tick(carry, t):
                state, outputs = carry
                recv = lax.ppermute(state, "pp", fwd_perm)
                k_c, m_c, w = tick_coords(t)

                params_k = self._select_chunk_params(local_stage_params, k_c)
                is_first_chunk = (d == 0) & (k_c == 0)
                inp = jnp.where(is_first_chunk, hidden_mb[m_c], recv)
                out, _ = stage_mod.apply(
                    {"params": params_k}, inp, mask_mb[m_c], **tick_rngs(t)
                )
                # idle ticks (bubble) compute on clamped inputs; their
                # outputs are never consumed by an active receiver, and
                # their writes (w clipped) are overwritten at t == C-1
                outputs = lax.dynamic_update_index_in_dim(
                    outputs, out, w, axis=0
                )
                return (out, outputs), None

            (_, outputs), _ = lax.scan(
                tick, (state, outputs), jnp.arange(T)
            )
            return outputs

        return self._run_ring_schedule(body, stage_params, hidden_mb,
                                       mask_mb, rng=rng)

    def _interleaved_grouped_encoder(self, stage_params, hidden_mb, mask_mb,
                                     rng=None):
        """Megatron-style grouped interleaving for M > S, S | M.

        Microbatches run in G = M/S groups of S.  Device d at tick t maps
        tau = t - d to (group g, slot k, offset i) = (tau // (V*S),
        (tau mod V*S) // S, tau mod S) and computes chunk c = k*S + d on
        microbatch m = g*S + i.  Dependency check: chunk c-1 of the same
        microbatch finishes on device d-1 (same slot) or device S-1 (slot
        k-1, offset i) exactly one tick earlier, so the uniform neighbor
        ppermute still delivers every transition on time.  Per-device
        bubble is (S-1)/V chunk-units vs (S-1) for plain GPipe: total
        ticks T = M*V + S - 1 of 1/V-sized chunks.

        Completed microbatches surface only at (d = S-1, k = V-1); all
        other ticks write to a scratch slot M that is sliced away.
        """
        S, V = self.num_stages, self.virtual_stages
        M = hidden_mb.shape[0]  # caller pads to a multiple of S
        if M % S != 0:
            raise ValueError(
                f"grouped interleaving needs microbatches ({M}) to be a "
                f"multiple of num_stages ({S})"
            )
        T = M * V + S - 1
        tp = self.tp
        stage_mod = self.tp_stage if tp > 1 else self.stage

        def body(local_stage_params, hidden_mb, mask_mb, *maybe_rng):
            local_stage_params = self._guard_tp_replicated(local_stage_params)
            d = lax.axis_index("pp")
            fwd_perm = [(i, (i + 1) % S) for i in range(S)]
            tick_rngs = self._stage_rng_stream(maybe_rng)

            def tick_coords(t):
                """tau -> (active, chunk slot k_c, microbatch m_c, done)."""
                tau = t - d
                g = tau // (V * S)  # floor division: negative while filling
                r = tau - g * (V * S)
                k = r // S
                i = r - k * S
                m = g * S + i
                active = (tau >= 0) & (m >= 0) & (m < M)
                k_c = jnp.clip(k, 0, V - 1)
                m_c = jnp.clip(m, 0, M - 1)
                done = active & (k_c == V - 1)
                return active, k_c, m_c, done

            if self.side_outputs:
                state = (jnp.zeros_like(hidden_mb[0]),
                         jnp.zeros_like(mask_mb[0]))
                outputs = (
                    jnp.zeros((M + 1,) + hidden_mb.shape[1:],
                              hidden_mb.dtype),
                    jnp.zeros((M + 1,) + mask_mb.shape[1:], mask_mb.dtype),
                )

                def tick_side(carry, t):
                    state, (out_h, out_s) = carry
                    recv_h, recv_s = lax.ppermute(state, "pp", fwd_perm)
                    active, k_c, m_c, done = tick_coords(t)
                    params_k = self._select_chunk_params(
                        local_stage_params, k_c
                    )
                    first = (d == 0) & (k_c == 0) & active
                    inp_h = jnp.where(first, hidden_mb[m_c], recv_h)
                    inp_s = jnp.where(first, mask_mb[m_c], recv_s)
                    h, s = stage_mod.apply(
                        {"params": params_k}, inp_h, inp_s, **tick_rngs(t)
                    )
                    w = jnp.where(done, m_c, M)
                    out_h = lax.dynamic_update_index_in_dim(out_h, h, w, 0)
                    out_s = lax.dynamic_update_index_in_dim(out_s, s, w, 0)
                    return ((h, s), (out_h, out_s)), None

                (_, (out_h, out_s)), _ = lax.scan(
                    tick_side, (state, outputs), jnp.arange(T)
                )
                return out_h[:M], out_s[:M]

            state = jnp.zeros_like(hidden_mb[0])
            # slot M is the scratch target for bubble/non-final writes
            outputs = jnp.zeros(
                (M + 1,) + hidden_mb.shape[1:], hidden_mb.dtype
            )

            def tick(carry, t):
                state, outputs = carry
                recv = lax.ppermute(state, "pp", fwd_perm)
                active, k_c, m_c, done = tick_coords(t)

                params_k = self._select_chunk_params(local_stage_params, k_c)
                is_first_chunk = (d == 0) & (k_c == 0)
                inp = jnp.where(is_first_chunk & active, hidden_mb[m_c],
                                recv)
                out, _ = stage_mod.apply(
                    {"params": params_k}, inp, mask_mb[m_c], **tick_rngs(t)
                )
                # only the final chunk's completions are real outputs
                w = jnp.where(done, m_c, M)
                outputs = lax.dynamic_update_index_in_dim(
                    outputs, out, w, axis=0
                )
                return (out, outputs), None

            (_, outputs), _ = lax.scan(
                tick, (state, outputs), jnp.arange(T)
            )
            return outputs[:M]

        return self._run_ring_schedule(body, stage_params, hidden_mb,
                                       mask_mb, rng=rng)

    def _check_rng(self, rng):
        """Stochastic engines require a key; deterministic ones ignore it."""
        if self.deterministic:
            return None
        if rng is None:
            raise ValueError(
                "this engine was built with deterministic=False (dropout "
                "active); pass rng= to train_step/loss/_logits"
            )
        return rng

    # --- full model ----------------------------------------------------------
    def _logits(self, params, input_ids, token_type_ids, attention_mask,
                rng=None):
        rng = self._check_rng(rng)
        sub = (
            (lambda i: None) if rng is None
            else (lambda i: {"dropout": jax.random.fold_in(rng, i)})
        )
        M = self.num_microbatches
        hidden, mask4 = self.embeddings.apply(
            {"params": params["embeddings"]},
            input_ids, token_type_ids, attention_mask,
            rngs=sub(0),
        )
        B = hidden.shape[0]
        if B % M != 0:
            raise ValueError(f"batch {B} not divisible by microbatches {M}")
        if (B // M) % self.dp != 0:
            raise ValueError(
                f"microbatch size {B // M} not divisible by dp={self.dp}"
            )
        hidden_mb = hidden.reshape(M, B // M, *hidden.shape[1:])
        mask_mb = mask4.reshape(M, B // M, *mask4.shape[1:])

        ring_rng = None if rng is None else jax.random.fold_in(rng, 1)
        if self.virtual_stages > 1:
            encoded = self._interleaved_encoder(
                params["stages"], hidden_mb, mask_mb, rng=ring_rng
            )
        else:
            encoded = self._pipelined_encoder(
                params["stages"], hidden_mb, mask_mb, rng=ring_rng
            )
        encoded = encoded.reshape(B, *encoded.shape[2:])

        pooled = self.pooler.apply(
            {"params": params["pooler"]}, encoded, mask4, rngs=sub(2)
        )
        return self.classifier.apply(
            {"params": params["classifier"]}, pooled, rngs=sub(3)
        )

    def loss(self, params, batch, labels, rng=None):
        input_ids, token_type_ids, attention_mask = batch
        logits = self._logits(
            params, input_ids, token_type_ids, attention_mask, rng=rng
        )
        return optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), labels
        ).mean()

    # --- training ------------------------------------------------------------
    def make_train_step(self):
        """The FULL train step — grad + update — as one jitted program."""
        jit_kwargs = {}
        if self.zero1:
            # pin the updated state to its ZeRO shards (and params to
            # theirs) so XLA reduce-scatters grads into the state update
            # instead of re-replicating
            if self.param_shardings is None or self.opt_shardings is None:
                raise RuntimeError(
                    "zero1=True needs init() and init_opt_state() before "
                    "make_train_step() — the step pins outputs to the "
                    "shardings those calls compute"
                )
            jit_kwargs["out_shardings"] = (
                self.param_shardings, self.opt_shardings, None
            )
        elif self.zero3:
            if self.param_shardings is None:
                raise RuntimeError(
                    "zero3=True needs init() before make_train_step() — "
                    "the step pins updated params to their dp shards"
                )
            jit_kwargs["out_shardings"] = (self.param_shardings, None, None)

        @functools.partial(jax.jit, donate_argnums=(0, 1), **jit_kwargs)
        def train_step(params, opt_state, batch, labels, rng=None):
            loss, grads = jax.value_and_grad(self.loss)(
                params, batch, labels, rng
            )
            if self.zero2:
                # pin each gradient leaf to the same dp shards a
                # ZeRO-sharded state tensor of that shape gets (params
                # keep their own shardings; only their GRADIENTS live
                # dp-sharded, so the full replicated grad buffer never
                # materializes — the cross-dp sum reduce-scatters
                # straight into shards)
                grads = jax.tree_util.tree_map(
                    lambda g: jax.lax.with_sharding_constraint(
                        g, self._zero1_sharding(g)
                    ),
                    grads,
                )
            updates, opt_state = self.optimizer.update(
                grads, opt_state, params
            )
            params = optax.apply_updates(params, updates)
            return params, opt_state, loss

        self._train_step = train_step
        return train_step

    def train_step(self, params, opt_state, batch, labels, rng=None):
        if self._train_step is None:
            self.make_train_step()
        if self.deterministic:
            if rng is not None:
                raise ValueError(
                    "rng= was passed but this engine is deterministic; "
                    "build it with deterministic=False to train with "
                    "dropout"
                )
            return self._train_step(params, opt_state, batch, labels)
        self._check_rng(rng)
        return self._train_step(params, opt_state, batch, labels, rng)


__all__ = [
    "CompiledBertPipeline",
    "EncoderStage",
    "TpEncoderStage",
    "TpEncoderUnit",
    "split_stage_params_for_tp",
    "merge_stage_params_from_tp",
]
