#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

One process drives the two main paths once, through the entry points a
user calls, at the full width of a model each supports, with random
weights made from a seed:

- **trainer** — ``experiment.launch.run`` on ``experiment/config.py``
  (config -> ParameterServer -> Model/DeviceBenchmarker ->
  ``Allocator.optimal_allocate`` -> ``PipelineModel`` -> ``Runner.train``):
  BERT-large as published (hidden 1024, 16 heads, intermediate 4096,
  vocab 30522, 24 layers), sequence 128, batch 32, bf16 compute, four
  logical stages, four microbatches, a few SGD steps on the config's own
  synthetic corpus.  Every loss finite, every stage's parameters moved by
  the update, no recompile after the first step, and the first step's
  loss and gradient norm equal to ONE ``jax.value_and_grad`` over the
  whole stack on the same batch with the same dropout keys (the engine
  threads cotangents through per-stage remat programs and accumulates
  over microbatches; the reference does neither).
- **flash** — one forward of the same stack and weights with
  ``use_flash_attention=True`` against the default einsum attention.
- **server** — ``ServingEngine`` on ``GptConfig()``
  (GPT-2 small as published), ``attn_impl`` left to auto so the compiled
  Pallas kernel runs, requests of mixed length that join and leave
  mid-decode, once on fp pages and once on int8 pages.  Every token
  emitted must be the argmax of a plain full forward over the same
  stream, to within a few bf16 ulps of the top logit (see
  ``FP_TIE_ULPS``); identity with ``generate_cached`` is reported, not
  required, because a bf16 LM head ties too often for two correct
  evaluations to agree token for token.  No recompile after warm-up.

It fails — non-zero exit, no result line — when JAX finds no TPU, when a
phase raises or a comparison misses its stated tolerance, or when a
Pallas kernel on the path was lowered in interpret mode (the compiled
program must hold a ``tpu_custom_call``).  ``--multichip`` runs instead,
on four chips: the trainer with one stage per chip (placement checked
array by array), its one-device reference, and one step of the compiled
SPMD pipeline as the collectives check.  ``--rehearse`` is the same
control flow at a tiny size on the CPU, for finding faults before a chip
is spent on them; it can never report a TPU.

Earlier stdout lines are one JSON object per phase; wall seconds in them
are smoke timings (what a cold run costs), not metrics.  The last line is
``{"ok": true, "device": {"platform", "kind", "count"}}`` as JAX reports
the device.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

# Stated tolerances, bf16 compute (8 mantissa bits, ~4e-3 per rounding).
# Engine and reference run the same math in differently fused programs,
# so they differ by accumulated bf16 rounding, not by algorithm; the fp32
# versions of these checks (tests/test_pipeline.py) hold 1e-5.
LOSS_RTOL = 1e-2
GRAD_NORM_RTOL = 5e-2
FLASH_LOGITS_ATOL = 5e-2
# Greedy streams.  The LM head emits bf16 logits over 50257 tokens, so the
# best two are often within an ulp or two of each other and two correct
# bf16 evaluations (the kernel's fp32 attention math, XLA's bf16 einsum)
# can rank them differently; after one such flip the streams differ for
# good.  So identity with generate_cached is reported, and what is
# REQUIRED is that every token the engine emitted is the reference's own
# argmax to within this many bf16 ulps of the top logit, the reference
# being the one-shot full forward over the engine's own stream.  A wrong
# kernel is off by the spread of the logits — hundreds of ulps.
FP_TIE_ULPS = 4
# int8 pages add quantization error on top (the repo's contract for them
# is bounded error, not identity: tests/test_serving.py)
INT8_TIE_ULPS = 16


def emit(**record) -> None:
    print(json.dumps(record), flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def rel_err(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


# --------------------------------------------------------------------------
# trainer
# --------------------------------------------------------------------------


def make_reference_step(stacks, loss_fn, num_microbatches):
    """One jitted ``value_and_grad`` over the whole stack for microbatch
    ``m``: ONE program (no per-stage remat, no host-threaded cotangents,
    no accumulation), taking and returning per-stage parameter lists.

    The stack is ``bert_layer_configs``'s: embeddings, L x (head, body,
    tail), pooler, classifier.  The L identical encoder layers run as a
    ``lax.scan`` over their stacked parameters, which keeps this
    verification program a twentieth the size of the unrolled one (it
    would otherwise be the largest executable of the run and push the
    system's own programs out of a size-capped compile cache).  Dropout
    keys follow the engine's rule, so both sides draw the same masks:
    unit ``i`` of stage ``k`` gets
    ``fold_in(fold_in(fold_in(fold_in(step_rng, m), k), i)``."""
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
    check(rest == 0 and num_layers >= 1,
          f"{len(modules)} units is not embeddings + 3L + pooler + head")

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


def register_smoke_hook():
    """The Runner's own extension point is how the smoke sees inside
    ``experiment.launch.run`` without changing it: a hook, named in the
    config's ``hook_config`` like any other."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from skycomputing_tpu.parallel.pipeline import _split_microbatches
    from skycomputing_tpu.registry import HOOKS
    from skycomputing_tpu.runner import Hook

    sq_norm = jax.jit(lambda tree: optax.global_norm(tree) ** 2)

    def small_leaves(stage):
        """Host copies of a stage's vector-sized parameters (biases,
        LayerNorm): enough to see the update land in every stage without
        pulling a gigabyte of kernels back."""
        return [
            np.asarray(x)
            for x in jax.tree_util.tree_leaves(stage.params)
            if x.size <= 1 << 16
        ]

    @HOOKS.register_module
    class ChipSmokeHook(Hook):
        def __init__(self, record):
            self.record = record

        def before_run(self, runner):
            model = runner.model
            self.record["model"] = model
            self.record["stage_layers"] = [
                s.num_layers for s in model.stages
            ]
            self.record["params_before"] = [
                small_leaves(s) for s in model.stages
            ]
            self.record["steps"] = []

        def before_train_iter(self, runner):
            if runner.iter != 0:
                return
            model = runner.model
            M = model.num_microbatches
            data, labels = runner.current_batch
            # the key Runner is about to hand train_step: the second
            # half of the next split of its checkpointable rng chain
            step_rng = jax.random.split(
                jax.random.wrap_key_data(jnp.asarray(runner.snapshot_rng()))
            )[1]

            grads, losses, _ = model.compute_gradients(
                data, labels, step_rng
            )
            pipe_loss = float(sum(jax.device_get(l) for l in losses))
            stage_sq = [float(sq_norm(g)) for g in grads]
            del grads

            # reference: everything on the first stage's device, before
            # the train step donates these parameter buffers away
            dev = model.stages[0].device
            params = [jax.device_put(s.params, dev) for s in model.stages]
            reference = make_reference_step(
                [s.stack for s in model.stages], model._loss_fn, M
            )
            t0 = time.perf_counter()
            ref_loss = 0.0
            micro = zip(_split_microbatches(tuple(data), M),
                        _split_microbatches(labels, M))
            total = None
            for m, (d, l) in enumerate(micro):
                loss, g = reference(params, d, l, step_rng, np.int32(m))
                ref_loss += float(loss)
                total = g if total is None else jax.tree_util.tree_map(
                    jnp.add, total, g
                )
            ref_sq = [float(sq_norm(g)) for g in total]
            self.record["reference_s"] = time.perf_counter() - t0
            self.record["first_batch"] = (data, labels)
            self.record["compare"] = dict(
                pipeline_loss=pipe_loss,
                reference_loss=ref_loss,
                pipeline_grad_norm=float(np.sqrt(sum(stage_sq))),
                reference_grad_norm=float(np.sqrt(sum(ref_sq))),
                pipeline_stage_grad_norms=[
                    float(np.sqrt(x)) for x in stage_sq
                ],
                reference_stage_grad_norms=[
                    float(np.sqrt(x)) for x in ref_sq
                ],
            )

        def after_train_iter(self, runner):
            stats = runner.model.stats
            self.record["steps"].append(dict(
                loss=float(stats.loss),
                wall_s=stats.forward_s + stats.backward_s + stats.step_s,
                compiles=int(stats.compiles),
                program_dispatches=int(stats.program_dispatches),
            ))

        def after_run(self, runner):
            self.record["params_after"] = [
                small_leaves(s) for s in runner.model.stages
            ]


def stage_placement(model, data):
    """Where each stage's parameters and activations actually live
    (``x.devices()``), found by walking one forward by hand."""
    import jax

    from skycomputing_tpu.builder import as_tuple

    placement = []
    acts = as_tuple(data)
    rng = jax.random.key(0)
    for k, stage in enumerate(model.stages):
        acts = stage.forward(acts, jax.random.fold_in(rng, k))
        param_devs = {
            str(d) for x in jax.tree_util.tree_leaves(stage.params)
            for d in x.devices()
        }
        act_devs = {str(d) for x in acts for d in x.devices()}
        placement.append(dict(
            stage=k, layers=stage.num_layers,
            assigned=str(stage.device),
            params_on=sorted(param_devs),
            activations_on=sorted(act_devs),
        ))
    jax.block_until_ready(acts)
    return placement


def trainer_phase(size, multichip: bool):
    import jax
    import numpy as np

    from experiment.launch import run
    from skycomputing_tpu import load_config
    from skycomputing_tpu.dynamics import native
    from skycomputing_tpu.parallel.pipeline import xla_compile_count
    from skycomputing_tpu.utils import Logger

    t_phase = time.perf_counter()
    compiles0 = xla_compile_count()
    log_root = os.path.join(ROOT, "chiprun_out", "chip_smoke_logs")
    # the config's own knobs, as a user would set them
    os.environ.update(
        SKYTPU_PRESET=size["bert_preset"],
        SKYTPU_LAYER_NUM=str(size["bert_layers"]),
        SKYTPU_CORE_NUM="4",
        SKYTPU_MICROBATCHES="4",
        SKYTPU_BATCH_SIZE=str(size["bert_batch"]),
        SKYTPU_SEQ_LEN=str(size["bert_seq"]),
        SKYTPU_MAX_ITERS=str(size["train_steps"]),
        SKYTPU_ALLOCATE_TYPE="optimal",
        SKYTPU_LOG_ROOT=log_root,
    )
    cfg = load_config(os.path.join(ROOT, "experiment", "config.py"))
    register_smoke_hook()
    record: dict = {}
    cfg.train_config["hook_config"].append(
        dict(type="ChipSmokeHook", record=record)
    )
    logger = Logger(**cfg.logging_config)
    rc = run(cfg, logger)
    check(rc == 0, f"experiment.launch.run returned {rc} "
                   f"(see {cfg.logging_config['filename']})")

    steps, cmp = record["steps"], record["compare"]
    model = record["model"]
    check(len(steps) == size["train_steps"],
          f"ran {len(steps)} of {size['train_steps']} steps")
    check(all(np.isfinite(s["loss"]) for s in steps),
          f"non-finite loss in {[s['loss'] for s in steps]}")
    check(all(s["compiles"] == 0 for s in steps[1:]),
          f"recompiled after the first step: "
          f"{[s['compiles'] for s in steps]}")
    moved = [
        sum(bool(np.any(a != b)) for a, b in zip(before, after))
        for before, after in zip(record["params_before"],
                                 record["params_after"])
    ]
    finite = all(
        np.all(np.isfinite(x))
        for stage in record["params_after"] for x in stage
    )
    check(finite and all(n > 0 for n in moved),
          f"update left a stage's parameters unchanged or non-finite "
          f"(changed small leaves per stage: {moved})")
    # the compared loss is the one Runner's first step itself reported
    check(steps[0]["loss"] == cmp["pipeline_loss"],
          f"first step loss {steps[0]['loss']} != the same pass "
          f"recomputed {cmp['pipeline_loss']}")
    loss_err = rel_err(cmp["pipeline_loss"], cmp["reference_loss"])
    norm_err = rel_err(cmp["pipeline_grad_norm"],
                       cmp["reference_grad_norm"])
    check(loss_err <= LOSS_RTOL and norm_err <= GRAD_NORM_RTOL,
          f"pipeline vs monolithic value_and_grad: {cmp}")

    placement = None
    if multichip:
        placement = stage_placement(model, record["first_batch"][0])
        homes = [tuple(p["params_on"]) for p in placement]
        check(
            all(len(h) == 1 for h in homes)
            and len(set(homes)) == len(homes)
            and all(p["params_on"] == p["activations_on"] == [p["assigned"]]
                    for p in placement),
            f"stages share a device or sit off their own: {placement}",
        )

    steady = sum(s["wall_s"] for s in steps[1:])
    wall = time.perf_counter() - t_phase
    emit(
        phase="trainer",
        model=f"bert-{size['bert_preset']}",
        shapes=dict(layers=size["bert_layers"], batch=size["bert_batch"],
                    seq=size["bert_seq"], stages=len(model.stages),
                    microbatches=model.num_microbatches,
                    layer_units_per_stage=record["stage_layers"]),
        solver="native" if native.load() is not None else "python",
        steps_done=len(steps),
        losses=[s["loss"] for s in steps],
        first_step=cmp,
        loss_rel_err=loss_err, loss_rtol=LOSS_RTOL,
        grad_norm_rel_err=norm_err, grad_norm_rtol=GRAD_NORM_RTOL,
        changed_small_leaves_per_stage=moved,
        compiles_per_step=[s["compiles"] for s in steps],
        backend_compiles=xla_compile_count() - compiles0,
        program_dispatches_per_step=steps[-1]["program_dispatches"],
        placement=placement,
        smoke_timing_s=dict(
            phase_wall=wall, steady_steps=steady,
            setup=wall - steady,
            reference=record["reference_s"],
        ),
    )
    return record


def flash_phase(size, record):
    """The flash-attention kernel against the default einsum attention:
    one deterministic forward of the trained stack's weights each."""
    import jax
    import numpy as np

    from skycomputing_tpu.builder import build_layer_stack
    from skycomputing_tpu.models import bert_config, bert_layer_configs

    t_phase = time.perf_counter()
    model = record["model"]
    data, _ = record["first_batch"]
    dev = model.stages[0].device
    params = [
        p for s in model.stages for p in jax.device_put(s.params, dev)
    ]
    data = jax.device_put(tuple(data), dev)

    def forward(use_flash: bool):
        cfg = bert_config(size["bert_preset"],
                          use_flash_attention=use_flash)
        stack = build_layer_stack(bert_layer_configs(
            cfg, num_encoder_units=size["bert_layers"], num_classes=3,
            deterministic=True,
        ))
        compiled = jax.jit(
            lambda p, *x: stack.apply(p, *x)
        ).lower(params, *data).compile()
        return np.asarray(compiled(params, *data), np.float32), \
            compiled.as_text()

    flash, flash_text = forward(True)
    plain, plain_text = forward(False)
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        check("tpu_custom_call" in flash_text,
              "flash attention ran without a compiled Pallas kernel")
        check("tpu_custom_call" not in plain_text,
              "the einsum baseline holds a Pallas kernel")
    diff = float(np.max(np.abs(flash - plain)))
    check(np.all(np.isfinite(flash)) and diff <= FLASH_LOGITS_ATOL,
          f"flash vs einsum logits differ by {diff}")
    emit(
        phase="flash",
        shapes=dict(logits=list(flash.shape), layers=size["bert_layers"]),
        kernel_compiled=on_tpu,
        max_abs_logit_diff=diff, atol=FLASH_LOGITS_ATOL,
        max_abs_logit=float(np.max(np.abs(plain))),
        smoke_timing_s=dict(phase_wall=time.perf_counter() - t_phase),
    )


# --------------------------------------------------------------------------
# server
# --------------------------------------------------------------------------


def build_gpt(size, seed: int):
    import jax
    import numpy as np

    from skycomputing_tpu.builder import build_layer_stack
    from skycomputing_tpu.models.gpt import GptConfig, gpt_layer_configs

    cfg = GptConfig(**size["gpt_overrides"])
    layer_cfgs = gpt_layer_configs(cfg, deterministic=True)
    stack = build_layer_stack(layer_cfgs)
    params = stack.init(jax.random.key(seed), np.ones((1, 8), np.int32))
    return cfg, layer_cfgs, stack, params


def reference_streams(stack, params, prompts, specs, context_length):
    """``generate_cached`` per request; requests that share a
    (prompt length, new tokens) shape share one compiled program."""
    import numpy as np

    from skycomputing_tpu.models.gpt import generate_cached

    out = [None] * len(prompts)
    for shape in sorted(set(specs)):
        rows = [i for i, s in enumerate(specs) if s == shape]
        batch = np.stack([prompts[i] for i in rows])
        done = generate_cached(stack, params, batch, shape[1],
                               context_length)
        for i, row in zip(rows, done):
            out[i] = np.asarray(row)
    return out


def make_argmax_gaps(stack, pad_to):
    """``(params, streams, prompt_lens) -> per stream, per generated
    token``: how many bf16 ulps of the top logit the token sits below the
    reference's best, the reference being ONE full forward (no cache, no
    kernel) over the whole stream; 0 = its argmax."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def gaps(params, ids):
        logits = stack.apply(params, ids)[:, :-1]  # position t predicts t+1
        top = jnp.max(logits, axis=-1)
        chosen = jnp.take_along_axis(
            logits, ids[:, 1:, None], axis=-1
        )[..., 0]
        ulp = 2.0 ** (jnp.floor(jnp.log2(jnp.abs(top))) - 7)
        return (top - chosen) / ulp

    def per_stream(params, streams, prompt_lens):
        ids = np.zeros((len(streams), pad_to), np.int32)
        for row, stream in zip(ids, streams):
            row[: len(stream)] = stream
        table = np.asarray(gaps(params, jnp.asarray(ids)))
        return [
            table[i, n - 1: len(stream) - 1]
            for i, (stream, n) in enumerate(zip(streams, prompt_lens))
        ]

    return per_stream


def server_phase(size, gpt, prompts, specs, refs, argmax_gaps, kv_dtype,
                 attn_impl):
    import jax
    import numpy as np

    from skycomputing_tpu.parallel.pipeline import xla_compile_count
    from skycomputing_tpu.serving import Request, ServingEngine

    t_phase = time.perf_counter()
    cfg, layer_cfgs, _, params = gpt
    compiles0 = xla_compile_count()
    engine = ServingEngine(
        layer_cfgs, params, kv_layout="paged", kv_dtype=kv_dtype,
        attn_impl=attn_impl, max_len=size["max_len"],
        buckets=size["buckets"], num_slots=4, page_size=16,
    )
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        check(engine.attn_impl == "pallas",
              f"auto attn_impl on a TPU chose {engine.attn_impl!r}")
        # the decode step exactly as _decode_tick shapes it
        stage, rows = engine.stages[0], engine.max_concurrency
        width = engine.max_pages_per_request
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, np.int32)
        text = stage.programs["decode_tick"].lower(
            stage.params, i32(rows, 1), stage.slabs, i32(rows, width),
            i32(rows), i32(rows),
        ).compile().as_text()
        check("tpu_custom_call" in text,
              "the paged decode step holds no compiled Pallas kernel "
              "(interpret mode on a TPU)")

    rng = np.random.default_rng(1000 + len(prompts))

    def request(length, new):
        prompt = rng.integers(1, cfg.vocab_size, (length,))
        return Request(prompt=prompt.astype(np.int32), max_new_tokens=new)

    # warm-up: one request per prefill bucket, decoded far enough to
    # cross every page-table width the steady wave will need
    engine.run([request(length, new) for length, new in size["warmup"]])
    warm_compiles = xla_compile_count() - compiles0
    t_warm = time.perf_counter()

    # steady wave: half the requests start, decode a few ticks, the rest
    # join mid-decode; short ones leave while long ones keep going
    wave = [Request(prompt=p.copy(), max_new_tokens=n)
            for p, (_, n) in zip(prompts, specs)]
    steady0 = xla_compile_count()
    half = len(wave) // 2
    for r in wave[:half]:
        engine.submit(r)
    for _ in range(4):
        engine.step()
    check(any(not r.done for r in wave[:half]),
          "nothing was mid-decode when the second half joined")
    for r in wave[half:]:
        engine.submit(r)
    engine.run()
    steady_compiles = xla_compile_count() - steady0
    t_done = time.perf_counter()

    check(all(r.status == "finished" for r in wave),
          f"request states: {[r.status for r in wave]}")
    check(steady_compiles == 0,
          f"{steady_compiles} recompiles in the steady wave after warm-up")
    streams = [np.asarray(r.output()) for r in wave]
    agree = total = 0
    identical, diverged_at = [], []
    for got, ref, (n, _) in zip(streams, refs, specs):
        check(got.shape == ref.shape,
              f"request produced {got.shape}, reference {ref.shape}")
        same = got[n:] == ref[n:]
        agree += int(same.sum())
        total += int(same.size)
        identical.append(bool(same.all()))
        diverged_at.append(None if same.all() else int(np.argmin(same)))
    gaps = argmax_gaps(params, streams, [n for n, _ in specs])
    worst = [float(g.max()) for g in gaps]
    tie_ulps = FP_TIE_ULPS if kv_dtype is None else INT8_TIE_ULPS
    check(max(worst) <= tie_ulps,
          f"a token sits {max(worst):.1f} bf16 ulps below the reference's "
          f"argmax (allowed {tie_ulps}); per stream: {worst}, identical "
          f"to generate_cached: {identical}")
    if kv_dtype is not None:
        check(engine.stats.quantized_pages > 0
              and engine.stats.dequant_blocks > 0,
              "int8 engine quantized nothing")
    engine._pool.check_consistency()
    emit(
        phase=f"server_{kv_dtype or 'fp'}",
        model="gpt2-small" if not size["gpt_overrides"] else "gpt-tiny",
        shapes=dict(vocab=cfg.vocab_size, hidden=cfg.hidden_size,
                    layers=cfg.num_hidden_layers,
                    heads=cfg.num_attention_heads, dtype=cfg.dtype,
                    decode_rows=engine.max_concurrency,
                    pages=engine.num_pages, page_size=engine.page_size,
                    max_len=engine.max_len,
                    buckets=list(engine.bucketer.buckets)),
        attn_impl=engine.attn_impl, kv_dtype=kv_dtype or cfg.dtype,
        kernel_compiled=on_tpu,
        requests_done=len(wave),
        prompt_lengths=[s[0] for s in specs],
        new_tokens=[s[1] for s in specs],
        tokens_compared=total, tokens_equal_generate_cached=agree,
        streams_identical_generate_cached=identical,
        first_divergence=diverged_at,
        worst_gap_below_reference_argmax_ulps=worst,
        allowed_gap_ulps=tie_ulps,
        tokens_at_reference_argmax=int(sum((g == 0).sum() for g in gaps)),
        warmup_compiles=warm_compiles, steady_compiles=steady_compiles,
        decode_tokens=engine.stats.decode_tokens,
        smoke_timing_s=dict(
            phase_wall=time.perf_counter() - t_phase,
            setup=t_warm - t_phase, steady=t_done - t_warm,
        ),
    )


# --------------------------------------------------------------------------
# four chips only
# --------------------------------------------------------------------------


def spmd_phase(size):
    """One step of the compiled SPMD pipeline over a four-device
    ('pp',) mesh: the ppermute ring and its transpose, on real links."""
    import jax
    import numpy as np

    from skycomputing_tpu.models import bert_config
    from skycomputing_tpu.parallel import make_pipeline_mesh
    from skycomputing_tpu.parallel.spmd import CompiledBertPipeline

    t_phase = time.perf_counter()
    cfg = bert_config(size["bert_preset"], hidden_dropout_prob=0.0,
                      attention_probs_dropout_prob=0.0)
    mesh = make_pipeline_mesh(4, jax.devices()[:4])
    pipe = CompiledBertPipeline(cfg, mesh, num_classes=3,
                                units_per_stage=size["spmd_units"],
                                num_microbatches=4)
    rng = np.random.default_rng(0)
    ids = rng.integers(5, cfg.vocab_size,
                       size=(size["bert_batch"], size["bert_seq"])
                       ).astype(np.int32)
    data = (ids, np.zeros_like(ids), np.ones_like(ids))
    labels = rng.integers(0, 3, size=(len(ids),)).astype(np.int32)
    params = pipe.init(jax.random.key(0), *data)
    opt_state = pipe.init_opt_state(params)
    new_params, _, loss = pipe.make_train_step()(
        params, opt_state, data, labels
    )
    loss = float(jax.block_until_ready(loss))
    check(np.isfinite(loss), f"SPMD step loss {loss}")
    spread = sorted({
        str(d) for x in jax.tree_util.tree_leaves(new_params)
        for d in x.devices()
    })
    check(len(spread) == 4, f"SPMD parameters live on {spread}")
    emit(
        phase="spmd",
        shapes=dict(mesh=dict(mesh.shape),
                    layers=4 * size["spmd_units"],
                    batch=size["bert_batch"], seq=size["bert_seq"]),
        loss=loss, params_on=spread,
        smoke_timing_s=dict(phase_wall=time.perf_counter() - t_phase),
    )


# --------------------------------------------------------------------------

FULL = dict(
    bert_preset="large", bert_layers=24, bert_batch=32, bert_seq=128,
    train_steps=4, spmd_units=2,
    gpt_overrides={},  # GptConfig() as published
    max_len=128, buckets=(16, 64),
    # (prompt length, new tokens): the first crosses 64 live tokens, so
    # decode warms both page-table widths (4 and 8 pages)
    warmup=((10, 60), (40, 4)),
    requests=((5, 24), (23, 12), (40, 30), (5, 24), (23, 12), (40, 30)),
)
TINY = dict(
    bert_preset="tiny", bert_layers=2, bert_batch=8, bert_seq=16,
    train_steps=3, spmd_units=1,
    gpt_overrides=dict(vocab_size=512, hidden_size=64,
                       num_hidden_layers=2, num_attention_heads=2,
                       max_position_embeddings=128),
    max_len=64, buckets=(8, 16),
    warmup=((5, 30), (12, 3)),
    requests=((3, 9), (7, 5), (12, 25), (3, 9), (7, 5), (12, 25)),
)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--multichip", action="store_true",
                        help="the four-chip phases only")
    parser.add_argument("--rehearse", action="store_true",
                        help="tiny sizes on the CPU backend")
    parser.add_argument("--seed", type=int, default=0,
                        help="weights and prompts of the server phase "
                             "(the trainer's come from its config)")
    args = parser.parse_args()
    need = 4 if args.multichip else 1
    if args.rehearse:
        # a rehearsal is a CPU run by construction, whatever is attached
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={need}"
            ).strip()
    size = TINY if args.rehearse else FULL

    import jax

    devices = jax.devices()
    if not args.rehearse and devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU — JAX reports {len(devices)} x "
              f"{devices[0].platform} ({devices[0].device_kind}); this "
              f"script only passes on the chip (--rehearse for the CPU "
              f"walk-through)", file=sys.stderr)
        return 2
    if len(devices) < need:
        print(f"chip_smoke: needs {need} devices, JAX reports "
              f"{len(devices)}", file=sys.stderr)
        return 2

    import numpy as np

    from skycomputing_tpu.parallel.pipeline import xla_compile_count
    from skycomputing_tpu.utils import enable_persistent_compilation_cache

    t0 = time.perf_counter()
    emit(
        phase="start",
        mode=("rehearsal " if args.rehearse else "")
        + ("multichip" if args.multichip else "one-chip"),
        jax=jax.__version__,
        compile_cache=enable_persistent_compilation_cache(),
        memory_stats_keys=sorted(devices[0].memory_stats() or {}),
    )
    record = trainer_phase(size, args.multichip)
    if args.multichip:
        spmd_phase(size)
    else:
        flash_phase(size, record)
        record.clear()  # the trainer's arrays: free the chip for the server
        gpt = gpt_cfg, _, stack, params = build_gpt(size, args.seed)
        rng = np.random.default_rng(args.seed)
        specs = list(size["requests"])
        prompts = [
            rng.integers(1, gpt_cfg.vocab_size, (length,)).astype(np.int32)
            for length, _ in specs
        ]
        refs = reference_streams(stack, params, prompts, specs,
                                 size["max_len"])
        # off the chip the rehearsal asks for the kernel by name (it
        # then runs interpreted); on it the engine's own choice stands
        impl = "pallas" if args.rehearse else None
        argmax_gaps = make_argmax_gaps(stack, size["max_len"])
        for kv_dtype in (None, "int8"):
            server_phase(size, gpt, prompts, specs, refs, argmax_gaps,
                         kv_dtype, impl)
    emit(phase="done", backend_compiles=xla_compile_count(),
         smoke_timing_s=dict(total_wall=time.perf_counter() - t0))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
