#!/usr/bin/env python
"""Measure KV-cache decoding speedup vs full-forward generate on device.

Evidence tool for the cached decoder (``models/gpt.py``): runs
GPT-2-small-scale decoding both ways, checks token identity, and prints
per-token timings.  Params are initialized host-side and moved in one
``device_put`` (eager layer-by-layer init pays one dispatch per
parameter).
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

from skycomputing_tpu.builder import build_layer_stack
from skycomputing_tpu.models.gpt import (
    GptConfig,
    generate,
    generate_cached,
    gpt_layer_configs,
)


def main() -> int:
    cfg = GptConfig(
        vocab_size=50257, hidden_size=768, num_hidden_layers=12,
        num_attention_heads=12, max_position_embeddings=512,
        dropout_prob=0.0,
    )
    stack = build_layer_stack(gpt_layer_configs(cfg, deterministic=True))
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, 50257, (4, 32)).astype(np.int32)
    print("initializing on host...", flush=True)
    with jax.default_device(jax.devices("cpu")[0]):
        params = stack.init(jax.random.key(0), prompt)
    params = jax.device_put(params, jax.devices()[0])
    fwd = jax.jit(lambda ids: stack.apply(params, ids))

    n_new = int(os.getenv("KV_TOKENS", "32"))
    ctx = int(os.getenv("KV_CTX", "256"))
    print("warming cached...", flush=True)
    generate_cached(stack, params, prompt, n_new, ctx)
    t0 = time.perf_counter()
    out_c = generate_cached(stack, params, prompt, n_new, ctx)
    tc = time.perf_counter() - t0
    print(f"cached: {tc:.3f}s total, {tc / n_new * 1e3:.2f} ms/token",
          flush=True)

    print("warming full...", flush=True)
    generate(fwd, prompt, 2, ctx)
    t0 = time.perf_counter()
    out_f = generate(fwd, prompt, n_new, ctx)
    tf = time.perf_counter() - t0
    print(f"full  : {tf:.3f}s total, {tf / n_new * 1e3:.2f} ms/token",
          flush=True)
    print(
        f"identical: {np.array_equal(out_c, out_f)} "
        f"speedup {tf / tc:.1f}x on {jax.devices()[0].device_kind}",
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
