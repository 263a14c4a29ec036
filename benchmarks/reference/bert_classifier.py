"""Plain reference for a training step of the BERT classifier: ONE
``jax.value_and_grad`` over the whole stack for one microbatch.  No
per-stage programs, no remat, no cotangents threaded by the host, no
accumulation.  (A copy of ``chip_smoke.py``'s ``make_reference_step``,
which passed on the v5e in PR 21; kept here so that no later PR can
change what ``correct`` is compared with.)

The stack is ``bert_layer_configs``'s: embeddings, L x (head, body,
tail), pooler, classifier.  The L identical encoder layers run as a
``lax.scan`` over their stacked parameters, which keeps this program a
twentieth of the unrolled one's size in the compile cache.  Dropout keys
follow the engine's rule, so both sides draw the same masks: unit ``i``
of stage ``k`` in microbatch ``m`` gets
``fold_in(fold_in(fold_in(step_rng, m), k), i)``.
"""

from __future__ import annotations

# stated tolerances, bf16 compute (8 mantissa bits, ~4e-3 a rounding):
# engine and reference run the same mathematics in differently fused
# programs, so they differ by accumulated bf16 rounding, not by algorithm
LOSS_RTOL = 1e-2
GRAD_NORM_RTOL = 5e-2


def make_reference_step(stacks, loss_fn, num_microbatches):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from skycomputing_tpu.builder import as_tuple

    modules = [mod for stack in stacks for mod in stack.modules]
    sizes = [len(stack.modules) for stack in stacks]
    stage_of = np.repeat(np.arange(len(sizes)), sizes)
    local = np.arange(len(modules)) - np.repeat(
        np.cumsum([0] + sizes[:-1]), sizes
    )
    num_layers, rest = divmod(len(modules) - 3, 3)
    if rest or num_layers < 1:
        raise ValueError(
            f"{len(modules)} units is not embeddings + 3L + pooler + head"
        )

    def unit(module, params, acts, key):
        return as_tuple(
            module.apply({"params": params}, *acts, rngs={"dropout": key})
        )

    def micro_loss(params_by_stage, data, labels, rng, m):
        flat = [p for stage in params_by_stage for p in stage]
        base = jax.random.fold_in(rng, m)
        keys = jax.vmap(
            lambda k, i: jax.random.fold_in(jax.random.fold_in(base, k), i)
        )(stage_of, local)
        acts = unit(modules[0], flat[0], data, keys[0])
        layers = [tuple(flat[1 + 3 * n: 4 + 3 * n])
                  for n in range(num_layers)]
        stacked = jax.tree_util.tree_map(
            lambda *leaves: jnp.stack(leaves), *layers
        )

        def layer(acts, xs):
            params, layer_keys = xs
            for j in range(3):
                acts = unit(modules[1 + j], params[j], acts, layer_keys[j])
            return acts, None

        acts, _ = jax.lax.scan(
            layer, acts, (stacked, keys[1:-2].reshape(num_layers, 3))
        )
        acts = unit(modules[-2], flat[-2], acts, keys[-2])
        acts = unit(modules[-1], flat[-1], acts, keys[-1])
        return loss_fn(acts[0], labels) / num_microbatches

    return jax.jit(jax.value_and_grad(micro_loss))
