"""Tiny Nemotron-H configurations and comparisons shared by the family's
two test files."""

import jax
import jax.numpy as jnp

from skycomputing_tpu.builder import build_layer_stack
from skycomputing_tpu.ops import causal_lm_loss


def tiny(pattern="ME*", dtype="float32", **over):
    cfg = dict(
        vocab_size=256, hidden_size=64, hybrid_override_pattern=pattern,
        num_hidden_layers=len(pattern), mamba_num_heads=4, mamba_head_dim=16,
        ssm_state_size=16, n_groups=2, conv_kernel=4, chunk_size=16,
        n_routed_experts=8, num_experts_per_tok=2, moe_intermediate_size=48,
        moe_shared_expert_intermediate_size=96, norm_topk_prob=True,
        routed_scaling_factor=2.5, experts_held_start=2, experts_held=4,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        norm_eps=1e-5, dtype=dtype,
    )
    cfg.update(over)
    return cfg


def layer_configs(cfg):
    from skycomputing_tpu.registry import LAYER

    LAYER.get_module("NemotronHBlock")  # the lazy import, as a config does
    from skycomputing_tpu.models.nemotron_h import nemotron_h_layer_configs

    return nemotron_h_layer_configs(cfg)


def built(cfg, batch=2, seq=40, seed=0):
    stack = build_layer_stack(layer_configs(cfg))
    ids = jax.random.randint(jax.random.key(seed + 1), (batch, seq), 0,
                             cfg["vocab_size"])
    params = stack.init(jax.random.key(seed), ids)
    # off their initial values, so that no term hides behind a zero or one
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.key(seed + 2), len(leaves))
    leaves = [a + 0.05 * jax.random.normal(k, a.shape, a.dtype)
              for a, k in zip(leaves, keys)]
    return stack, jax.tree_util.tree_unflatten(tree, leaves), ids


def program_loss(stack, params, ids):
    return causal_lm_loss(stack.apply(params, ids), ids)


def close(a, b, rtol):
    scale = float(jnp.abs(b).max()) + 1e-30
    return float(jnp.abs(a - b).max()) / scale <= rtol
