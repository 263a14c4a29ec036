#!/usr/bin/env python
"""Single-chip step time + MFU for the flagship BERT train step.

Measures the monolithic BERT train step (forward + backward + SGD update,
one jitted program) on the real chip, reads the exact FLOP count from XLA's
``cost_analysis()``, and reports MFU against the chip's peak.

    python tools/bench_mfu.py            # BERT-large, batch 32, seq 128
    SKYTPU_MFU_PRESET=base SKYTPU_MFU_BATCH=64 python tools/bench_mfu.py

Also times one encoder pipeline stage (fwd+bwd) in isolation — the number
the allocator's schedule model consumes.

Peak numbers: bf16 FLOP/s per chip from published TPU specs; a
device_kind the table misses is an error, not a default.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np
import optax

# bf16 peak FLOP/s by device_kind substring (published spec sheets)
PEAK_TFLOPS = {
    "v5 lite": 197.0,  # v5e
    "v5e": 197.0,
    "v5p": 459.0,
    "v4": 275.0,
    "v6 lite": 918.0,  # v6e / Trillium
    "v6e": 918.0,
}


def peak_flops(device) -> float:
    kind = device.device_kind.lower()
    for key, tflops in PEAK_TFLOPS.items():
        if key in kind:
            return tflops * 1e12
    raise SystemExit(
        f"unknown device kind {device.device_kind!r}: add its published "
        f"bf16 peak to PEAK_TFLOPS (known: {sorted(PEAK_TFLOPS)})"
    )


def timed(fn, *args, iters=10, warmup=2):
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / iters)
    return best


def main() -> int:
    from skycomputing_tpu.builder import build_layer_stack
    from skycomputing_tpu.models import bert_config, bert_layer_configs
    from skycomputing_tpu.ops import cross_entropy_loss

    preset = os.getenv("SKYTPU_MFU_PRESET", "large")
    batch = int(os.getenv("SKYTPU_MFU_BATCH", "32"))
    seq = int(os.getenv("SKYTPU_MFU_SEQ", "128"))
    units = int(os.getenv("SKYTPU_MFU_UNITS", "0")) or None

    device = jax.devices()[0]
    peak = peak_flops(device)
    cfg = bert_config(preset, hidden_dropout_prob=0.0,
                      attention_probs_dropout_prob=0.0)
    layer_cfgs = bert_layer_configs(
        cfg, num_encoder_units=units or cfg.num_hidden_layers,
        num_classes=3, deterministic=True,
    )
    stack = build_layer_stack(layer_cfgs)

    rng = np.random.default_rng(0)
    ids = rng.integers(5, cfg.vocab_size, (batch, seq)).astype(np.int32)
    types = np.zeros_like(ids)
    mask = np.ones_like(ids)
    labels = rng.integers(0, 3, (batch,)).astype(np.int32)

    print(f"initializing {preset} on host...", flush=True)
    with jax.default_device(jax.devices("cpu")[0]):
        params = stack.init(jax.random.key(0), ids, types, mask)
    params = jax.device_put(params, device)

    opt = optax.sgd(1e-3)
    opt_state = jax.device_put(opt.init(params), device)

    def loss_fn(params, ids, types, mask, labels):
        logits = stack.apply(params, ids, types, mask)
        return cross_entropy_loss(logits, labels)

    def train_step(params, opt_state, ids, types, mask, labels):
        loss, grads = jax.value_and_grad(loss_fn)(
            params, ids, types, mask, labels
        )
        updates, opt_state = opt.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state, loss

    step = jax.jit(train_step, donate_argnums=(0, 1))
    lowered = step.lower(params, opt_state, ids, types, mask, labels)
    print("compiling train step...", flush=True)
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    flops = float(cost.get("flops", 0.0))

    def run(params, opt_state):
        params, opt_state, loss = step(params, opt_state, ids, types, mask,
                                       labels)
        return params, opt_state, loss

    # donation means params/opt_state thread through the timing loop
    print("timing...", flush=True)
    for _ in range(2):
        params, opt_state, loss = run(params, opt_state)
    jax.block_until_ready(loss)
    iters = int(os.getenv("SKYTPU_MFU_ITERS", "10"))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            params, opt_state, loss = run(params, opt_state)
        jax.block_until_ready(loss)
        best = min(best, (time.perf_counter() - t0) / iters)

    mfu = flops / best / peak
    print(
        f"BERT-{preset} train step (B={batch}, L={seq}): {best * 1e3:.2f} ms"
        f" | {flops / 1e12:.2f} TFLOPs (XLA cost_analysis)"
        f" | {flops / best / 1e12:.1f} TFLOP/s achieved"
        f" | peak {peak / 1e12:.0f} TFLOP/s ({device.device_kind})"
        f" | MFU {mfu * 100:.1f}%",
        flush=True,
    )
    json_path = os.getenv("SKYTPU_MFU_JSON")
    if json_path:
        import json

        with open(json_path, "w") as fh:
            json.dump(
                {
                    "metric": (
                        f"BERT-{preset} monolithic train-step MFU "
                        f"(B={batch}, L={seq}) on {device.device_kind}"
                    ),
                    "value": round(mfu * 100, 2),
                    "unit": "percent",
                    "step_time_ms": round(best * 1e3, 3),
                    "tflops_per_step": round(flops / 1e12, 3),
                    "achieved_tflops_per_s": round(flops / best / 1e12, 2),
                    "peak_tflops_per_s": round(peak / 1e12, 1),
                    "device_kind": device.device_kind,
                    "platform": device.platform,
                },
                fh,
            )
            fh.write("\n")
        print(f"wrote {json_path}", flush=True)

    # one encoder stage (fwd+bwd) in isolation: the allocator's unit of time
    from skycomputing_tpu.parallel.spmd import EncoderStage

    stage = EncoderStage(cfg.to_dict(), units=1)
    hidden = jax.device_put(
        rng.standard_normal((batch, seq, cfg.hidden_size)).astype(
            np.dtype(cfg.dtype) if cfg.dtype != "bfloat16" else np.float32
        ),
        device,
    )
    if cfg.dtype == "bfloat16":
        import jax.numpy as jnp

        hidden = hidden.astype(jnp.bfloat16)
    mask4 = jax.device_put(np.zeros((batch, 1, 1, seq), np.float32), device)
    with jax.default_device(jax.devices("cpu")[0]):
        sparams = stage.init({"params": jax.random.key(1)}, hidden, mask4)[
            "params"
        ]
    sparams = jax.device_put(sparams, device)

    def stage_fwd_bwd(p, h):
        def f(p):
            out, _ = stage.apply({"params": p}, h, mask4)
            return (out.astype(np.float32) ** 2).mean()

        return jax.value_and_grad(f)(p)

    sstep = jax.jit(stage_fwd_bwd)
    scost = sstep.lower(sparams, hidden).compile().cost_analysis()
    st = timed(sstep, sparams, hidden)
    sflops = float(scost.get("flops", 0.0))
    print(
        f"encoder stage fwd+bwd (1 trio): {st * 1e3:.2f} ms"
        f" | {sflops / 1e9:.1f} GFLOPs | MFU {sflops / st / peak * 100:.1f}%",
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
