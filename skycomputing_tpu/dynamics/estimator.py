"""Static + timed measurement helpers.

TPU-native replacement for the reference ``Estimator``
(``scaelum/dynamics/estimator.py:15-152``):

- FLOPs come from XLA's own cost model
  (``jit(f).lower(...).compile().cost_analysis()['flops']``) instead of
  pthflops' torch-JIT tracing;
- memory uses the same accounting *formula* as the reference (param_scale x
  params + 2 x outputs + inputs, 4-byte floats, MB units) so the allocator
  interface is unchanged, but sizes are exact from avals instead of hook
  guesswork;
- speed measurement respects XLA async dispatch: warm-up compile, then
  ``block_until_ready`` timing.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _as_tuple(data) -> Tuple:
    return data if isinstance(data, tuple) else (data,)


def _aval_bytes(tree, bytes_per_number: float = None) -> float:
    total = 0.0
    for leaf in jax.tree_util.tree_leaves(tree):
        n = float(np.prod(leaf.shape)) if leaf.shape else 1.0
        itemsize = (
            bytes_per_number
            if bytes_per_number is not None
            else jnp.dtype(leaf.dtype).itemsize
        )
        total += n * itemsize
    return total


class Estimator:
    """Stateless measurement helpers (kept as a namespace class for parity)."""

    @staticmethod
    def benchmark_speed(
        fn: Callable,
        args: Sequence[Any],
        device=None,
        iterations: int = 30,
        warmup: int = 3,
    ) -> float:
        """Total wall-clock of ``iterations`` executions of jitted ``fn``.

        Honest timing on an async, compiled runtime requires placing inputs on
        the target device, compiling + warming up first, and blocking on the
        final output (reference analog: 30 no-grad forwards,
        ``estimator.py:15-34``).
        """
        jitted = jax.jit(fn)
        if device is not None:
            args = jax.device_put(list(args), device)
        out = None
        for _ in range(max(warmup, 1)):
            out = jitted(*args)
        jax.block_until_ready(out)

        start = time.perf_counter()
        for _ in range(iterations):
            out = jitted(*args)
        jax.block_until_ready(out)
        return time.perf_counter() - start

    @staticmethod
    def benchmark_model(
        module,
        data: Sequence[Any],
        param_scale: int = 2,
        rng: jax.Array = None,
    ):
        """(output_avals, flops, mem_MB) for one layer — fully static.

        No parameters are materialized and no FLOPs are executed: ``init`` and
        ``apply`` are shape-traced with ``jax.eval_shape`` and FLOPs come from
        compiling the apply against abstract inputs.  This is what lets the
        model benchmarker profile a 160-layer BERT without OOM — the
        reference needed a hard-coded BERT shortcut for that
        (``benchmarker.py:163-166``).
        """
        if rng is None:
            rng = jax.random.key(0)
        data = _as_tuple(data)
        avals = tuple(
            jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype)
            if not isinstance(x, jax.ShapeDtypeStruct)
            else x
            for x in data
        )

        k_params, k_dropout = jax.random.split(rng)
        variables_aval = jax.eval_shape(
            lambda *xs: module.init(
                {"params": k_params, "dropout": k_dropout}, *xs
            ),
            *avals,
        )
        params_aval = variables_aval["params"]

        def apply_fn(params, *xs):
            return module.apply(
                {"params": params}, *xs, rngs={"dropout": k_dropout}
            )

        out_aval = jax.eval_shape(apply_fn, params_aval, *avals)

        compiled = jax.jit(apply_fn).lower(params_aval, *avals).compile()
        flops = float(compiled.cost_analysis().get("flops", 0.0))

        mb = 1024.0**2
        # Reference formula (estimator.py:85-152): inputs + 2x outputs (grads)
        # + param_scale x params, at 4 bytes/number.
        input_size = _aval_bytes(avals, 4.0) / mb
        output_size = 2.0 * _aval_bytes(out_aval, 4.0) / mb
        param_size = param_scale * _aval_bytes(params_aval, 4.0) / mb
        mem_usage = input_size + output_size + param_size

        return out_aval, flops, mem_usage

    @staticmethod
    def estimate_memory(module, data: Sequence[Any], param_scale: int = 2,
                        rng: jax.Array = None):
        """(output_avals, mem_MB) — the static memory half of
        :meth:`benchmark_model` without the FLOPs compile (for callers
        that already measure cost some other way, e.g. timed profiling)."""
        if rng is None:
            rng = jax.random.key(0)
        data = _as_tuple(data)
        avals = tuple(
            jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype)
            if not isinstance(x, jax.ShapeDtypeStruct)
            else x
            for x in data
        )
        k_params, k_dropout = jax.random.split(rng)
        variables_aval = jax.eval_shape(
            lambda *xs: module.init(
                {"params": k_params, "dropout": k_dropout}, *xs
            ),
            *avals,
        )
        params_aval = variables_aval["params"]
        out_aval = jax.eval_shape(
            lambda params, *xs: module.apply(
                {"params": params}, *xs, rngs={"dropout": k_dropout}
            ),
            params_aval, *avals,
        )
        mb = 1024.0**2
        mem_usage = (
            _aval_bytes(avals, 4.0) / mb
            + 2.0 * _aval_bytes(out_aval, 4.0) / mb
            + param_scale * _aval_bytes(params_aval, 4.0) / mb
        )
        return out_aval, mem_usage

    @staticmethod
    def measure_flops(fn: Callable, *args) -> float:
        """XLA-reported FLOPs of an arbitrary jittable function."""
        compiled = jax.jit(fn).lower(*args).compile()
        return float(compiled.cost_analysis().get("flops", 0.0))

    @staticmethod
    def benchmark_decode_step(
        module,
        data: Sequence[Any],
        cache_avals: Optional[Sequence[Any]] = None,
        index: Any = None,
        param_scale: int = 2,
        rng: jax.Array = None,
    ):
        """(out_avals, flops, mem_MB) for ONE decode iteration — static.

        The serving counterpart of :meth:`benchmark_model`: training
        costs (full-sequence fwd+bwd) mis-rank layers for a *decode*
        partition, where attention is dominated by the KV-cache read
        (``O(max_len)`` per token) and everything else by ``Lq=1``
        matmuls.  This profiles the layer's actual per-token program:

        - attention-style layers (``cache_avals`` given): the layer's
          ``decode(data..., k_cache, v_cache, index)`` method against
          the full slot slab;
        - embedding-style layers (a ``decode`` method, no caches):
          ``decode(data..., index)``;
        - everything else: plain ``apply``.

        Like :meth:`benchmark_model`, everything is abstract — shapes
        via ``eval_shape``, FLOPs from XLA's cost model — so a deep
        stack profiles without materializing parameters.  ``mem_MB``
        is the reference accounting formula (inputs + 2x outputs +
        ``param_scale`` x params, 4 bytes); the *preallocated KV-slab*
        memory is deliberately not included here — it is a pool-level
        quantity added by the serving profile
        (:func:`~..serving.kv_cache.kv_mb_per_layer`), which keeps one
        slab-size formula shared with the pre-flight plan verifier.
        """
        if rng is None:
            rng = jax.random.key(0)
        data = _as_tuple(data)
        avals = tuple(
            jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype)
            if not isinstance(x, jax.ShapeDtypeStruct)
            else x
            for x in data
        )
        method = None
        args = avals
        if cache_avals is not None:
            method = type(module).decode
            args = avals + tuple(cache_avals) + (index,)
        elif hasattr(module, "decode"):
            method = type(module).decode
            args = avals + (index,)

        k_params, k_dropout = jax.random.split(rng)
        variables_aval = jax.eval_shape(
            lambda *xs: module.init(
                {"params": k_params, "dropout": k_dropout}, *xs,
                method=method,
            ),
            *args,
        )
        params_aval = variables_aval["params"]

        def step_fn(params, *xs):
            return module.apply({"params": params}, *xs, method=method)

        out_aval = jax.eval_shape(step_fn, params_aval, *args)
        compiled = jax.jit(step_fn).lower(params_aval, *args).compile()
        flops = float(compiled.cost_analysis().get("flops", 0.0))

        # memory counts the DATA outputs only: an attention decode also
        # returns the updated caches, but those alias the preallocated
        # slab (in-place update), not fresh per-step activations
        data_out = out_aval[0] if cache_avals is not None else out_aval
        mb = 1024.0**2
        mem_usage = (
            _aval_bytes(avals, 4.0) / mb
            + 2.0 * _aval_bytes(data_out, 4.0) / mb
            + param_scale * _aval_bytes(params_aval, 4.0) / mb
        )
        return out_aval, flops, mem_usage

    @staticmethod
    def benchmark_train_time(
        module,
        data: Sequence[Any],
        rng: jax.Array = None,
        iterations: int = 8,
        warmup: int = 2,
        repeats: int = 3,
        device=None,
    ) -> Tuple[Any, float]:
        """(outputs, measured fwd+bwd seconds per iteration) for one layer.

        The *timed* counterpart of :meth:`benchmark_model`: builds real
        params, jits one forward+backward (gradients w.r.t. params and
        inputs — what a pipeline stage actually computes each tick), warms
        the executable, then takes the best of ``repeats`` timed loops of
        ``iterations`` chained executions with one final block, matching
        the discipline of ``PipelineModel.measure_stage_times`` so
        allocator inputs and realized stage times live on the same scale.
        XLA's static FLOP count is a poor proxy for wall time on
        memory-bound units (softmax/LayerNorm-heavy attention thirds vs
        matmul-heavy FFN thirds), which mis-ranks layers for the
        allocator; measuring closes that gap.
        """
        if rng is None:
            rng = jax.random.key(0)
        data = _as_tuple(data)
        if device is not None:
            data = tuple(jax.device_put(x, device) for x in data)
        k_params, k_dropout = jax.random.split(rng)
        variables = module.init(
            {"params": k_params, "dropout": k_dropout}, *data
        )
        params = variables["params"]
        if device is not None:
            params = jax.device_put(params, device)

        def apply_fn(params, *xs):
            return module.apply(
                {"params": params}, *xs, rngs={"dropout": k_dropout}
            )

        # Time what a pipeline stage computes each tick: the forward
        # OUTPUTS (handed downstream — returned so XLA cannot dead-code
        # any of the forward) plus the vjp against a full-size cotangent,
        # w.r.t. params and the FLOAT inputs (upstream cotangents; integer
        # inputs like token ids are non-differentiable pass-throughs).  A
        # ``grad(sum(out))`` objective would let XLA elide most of the
        # forward — gradients of linear ops don't need their outputs.
        is_diff = tuple(
            jnp.issubdtype(np.asarray(x).dtype, np.inexact) for x in data
        )

        def train_like(params, diff_xs, int_xs, cotangent):
            def fwd(params, diff_xs):
                xs, di, ii = [], iter(diff_xs), iter(int_xs)
                for d in is_diff:
                    xs.append(next(di) if d else next(ii))
                return apply_fn(params, *xs)

            out, vjp = jax.vjp(fwd, params, diff_xs)
            return out, vjp(cotangent)

        outputs = apply_fn(params, *data)
        diff_xs = tuple(x for x, d in zip(data, is_diff) if d)
        int_xs = tuple(x for x, d in zip(data, is_diff) if not d)

        def fwd_shapes(params, diff_xs, int_xs):
            xs, di, ii = [], iter(diff_xs), iter(int_xs)
            for d in is_diff:
                xs.append(next(di) if d else next(ii))
            return apply_fn(params, *xs)

        # cotangent dtypes must match the TRACED outputs — weak-type
        # promotion differs between closed-over constants and traced
        # arguments, so eval_shape must receive every input as an
        # argument, exactly like the jitted step below does
        cotangent = jax.tree_util.tree_map(
            lambda a: (
                jnp.ones(a.shape, a.dtype)
                if jnp.issubdtype(a.dtype, jnp.inexact)
                else np.zeros(a.shape, jax.dtypes.float0)
            ),
            jax.eval_shape(fwd_shapes, params, diff_xs, int_xs),
        )
        step = jax.jit(train_like)
        result = None
        for _ in range(max(warmup, 1)):
            result = step(params, diff_xs, int_xs, cotangent)
        jax.block_until_ready(result)

        best = float("inf")
        for _ in range(repeats):
            start = time.perf_counter()
            for _ in range(iterations):
                result = step(params, diff_xs, int_xs, cotangent)
            jax.block_until_ready(result)
            best = min(best, (time.perf_counter() - start) / iterations)
        return outputs, best


__all__ = ["Estimator"]
