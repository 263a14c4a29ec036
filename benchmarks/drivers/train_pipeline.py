"""Driver: training through ``experiment.launch.run``.

The path a user takes (config -> ParameterServer -> profilers ->
``Allocator`` -> ``PipelineModel`` -> ``Runner.train``), exactly as
``chip_smoke.py``'s trainer phase drives it, with ONE hook named in the
config's ``hook_config``.  The hook compares the first step with the
plain reference (set-up), discards a few steps, opens the window, stamps
the end of every iteration, and asks the runner to stop when the time is
up.  Everything the hook does inside the window is a clock read and a
few list appends.
"""

from __future__ import annotations

import math
import os
import time

from ..harness import peaks
from ..reference import bert_classifier as reference

_SIZE_KEYS = ("hidden_size", "num_attention_heads", "intermediate_size",
              "vocab_size", "max_position_embeddings")


def _rel_err(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def _register_hook():
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from skycomputing_tpu.parallel.pipeline import (
        _split_microbatches,
        xla_compile_count,
    )
    from skycomputing_tpu.registry import HOOKS
    from skycomputing_tpu.runner import Hook

    sq_norm = jax.jit(lambda tree: optax.global_norm(tree) ** 2)

    @HOOKS.register_module
    class BenchTrainHook(Hook):
        def __init__(self, state):
            self.s = state
            self.ctx = state["ctx"]
            self._mark = None

        # -- set-up: the first step against the plain reference ---------
        def _compare_first_step(self, runner):
            model = runner.model
            M = model.num_microbatches
            data, labels = runner.current_batch
            # the key Runner is about to hand train_step: the second half
            # of the next split of its checkpointable rng chain
            step_rng = jax.random.split(
                jax.random.wrap_key_data(jnp.asarray(runner.snapshot_rng()))
            )[1]
            grads, losses, _ = model.compute_gradients(data, labels, step_rng)
            pipe_loss = float(sum(jax.device_get(l) for l in losses))
            stage_sq = [float(sq_norm(g)) for g in grads]
            del grads
            dev = model.stages[0].device
            params = [jax.device_put(s.params, dev) for s in model.stages]
            step = reference.make_reference_step(
                [s.stack for s in model.stages], model._loss_fn, M
            )
            t0 = time.perf_counter()
            ref_loss, total = 0.0, None
            micro = zip(_split_microbatches(tuple(data), M),
                        _split_microbatches(labels, M))
            for m, (d, l) in enumerate(micro):
                loss, g = step(params, d, l, step_rng, np.int32(m))
                ref_loss += float(loss)
                total = g if total is None else jax.tree_util.tree_map(
                    jnp.add, total, g
                )
            ref_sq = [float(sq_norm(g)) for g in total]
            self.s["compare"] = dict(
                pipeline_loss=pipe_loss, reference_loss=ref_loss,
                pipeline_grad_norm=float(np.sqrt(sum(stage_sq))),
                reference_grad_norm=float(np.sqrt(sum(ref_sq))),
                reference_s=time.perf_counter() - t0,
            )

        def before_run(self, runner):
            self.s["t_before_run"] = time.perf_counter()
            self.s["stage_layers"] = [
                s.num_layers for s in runner.model.stages
            ]

        def before_train_iter(self, runner):
            if runner.iter == 0:
                self.s["t_first_iter"] = time.perf_counter()
                self._compare_first_step(runner)
                self.s["t_compared"] = time.perf_counter()
            self._mark = self.ctx.tracer.mark()
            self._mark.__enter__()

        def after_train_iter(self, runner):
            self._mark.__exit__(None, None, None)
            now = time.perf_counter()
            s, stats = self.s, runner.model.stats
            if s["t_open"] is None:
                s["warm_losses"].append(float(stats.loss))
                if runner.iter >= s["discard"]:
                    s["t_open"] = now
                    s["loads_open"] = self.ctx.loads.count
                    s["compiles_open"] = xla_compile_count()
                    self.ctx.tracer.open(now)
                return
            s["stamps"].append(now)
            s["losses"].append(float(stats.loss))
            s["dispatch_s"].append(stats.dispatch_s)
            s["program_dispatches"].append(int(stats.program_dispatches))
            if now - s["t_open"] >= self.ctx.seconds:
                s["loads_close"] = self.ctx.loads.count
                s["compiles_close"] = xla_compile_count()
                self.ctx.tracer.stop()
                runner.request_stop()
            else:
                self.ctx.tracer.poll(now)


def run(ctx) -> dict:
    config, mix = ctx.config, ctx.traffic
    launch = config["launch"]
    log_root = os.path.join(ctx.out_dir, "logs")
    # the config's own knobs, as a user would set them; iteration and
    # epoch limits out of reach (the hook stops the run)
    os.environ.update(
        SKYTPU_MODEL="bert",
        SKYTPU_PRESET=launch["preset"],
        SKYTPU_LAYER_NUM=str(config["num_hidden_layers"]),
        SKYTPU_CORE_NUM=str(mix["stages"]),
        SKYTPU_MICROBATCHES=str(mix["microbatches"]),
        SKYTPU_BATCH_SIZE=str(mix["batch_size"]),
        SKYTPU_SEQ_LEN=str(mix["seq_len"]),
        SKYTPU_ALLOCATE_TYPE=mix["allocate_type"],
        SKYTPU_SCHEDULE=mix["schedule"],
        SKYTPU_OPTIM=config["training"]["optimizer"],
        SKYTPU_LR=str(config["training"]["learning_rate"]),
        SKYTPU_MAX_ITERS=str(10 ** 9),
        SKYTPU_MAX_EPOCHS=str(10 ** 9),
        SKYTPU_LOG_ROOT=log_root,
    )
    for name in ("SKYTPU_GLUE_DIR", "SKYTPU_VOCAB_FILE", "STIMULATE"):
        os.environ.pop(name, None)  # the synthetic corpus, no stimulator

    from experiment.launch import run as launch_run
    from skycomputing_tpu import load_config
    from skycomputing_tpu.models import bert_config
    from skycomputing_tpu.utils import Logger, compilation_cache_dir
    from ..harness.runtime import dir_bytes

    # the file states the sizes as run: hold the preset to it
    built = bert_config(launch["preset"])
    differ = {k: (config[k], getattr(built, k)) for k in _SIZE_KEYS
              if config[k] != getattr(built, k)}
    if differ or built.dtype != config["training"]["compute_dtype"]:
        raise RuntimeError(
            f"preset {launch['preset']!r} is not the configuration in the "
            f"file: {differ or built.dtype}"
        )

    cfg = load_config(os.path.join(ctx.root, "experiment", "config.py"))
    # --seed reaches what the path lets a caller seed: the loader's shuffle
    cfg.data_config["dataloader_cfg"]["seed"] = ctx.seed
    _register_hook()
    state = dict(
        ctx=ctx, discard=int(mix["discard_steps"]), t_open=None,
        warm_losses=[], stamps=[], losses=[], dispatch_s=[],
        program_dispatches=[],
    )
    cfg.train_config["hook_config"].append(
        dict(type="BenchTrainHook", state=state)
    )
    t_launch = time.perf_counter()
    rc = launch_run(cfg, Logger(**cfg.logging_config))
    if rc != 0 or not state["stamps"]:
        raise RuntimeError(
            f"experiment.launch.run returned {rc} with "
            f"{len(state['stamps'])} steps in the window "
            f"(see {cfg.logging_config['filename']})"
        )

    reduced = ctx.tracer.reduce()  # outside the window
    cmp = state["compare"]
    loss_err = _rel_err(cmp["pipeline_loss"], cmp["reference_loss"])
    norm_err = _rel_err(cmp["pipeline_grad_norm"],
                        cmp["reference_grad_norm"])
    loads = state["loads_close"] - state["loads_open"]
    compiles = state["compiles_close"] - state["compiles_open"]
    failed = sum(1 for x in state["losses"] if not math.isfinite(x))
    same_pass = state["warm_losses"][0] == cmp["pipeline_loss"]
    correct = (
        loss_err <= reference.LOSS_RTOL
        and norm_err <= reference.GRAD_NORM_RTOL
        and same_pass and failed == 0 and loads == 0 and compiles == 0
    )
    stamps = [state["t_open"]] + state["stamps"]
    steps_ms = [(b - a) * 1e3 for a, b in zip(stamps, stamps[1:])]
    flops = peaks.bert_train_step_flops(
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        batch=mix["batch_size"], seq=mix["seq_len"],
        num_classes=config["num_classes"],
    )
    from ..harness.stats import median, percentile

    cache_dir = compilation_cache_dir()
    ctx.emit(
        event="train_window",
        stage_layer_units=state["stage_layers"],
        steps=len(steps_ms), window_s=stamps[-1] - stamps[0],
        step_ms_median=median(steps_ms), step_ms_p95=percentile(steps_ms, 95),
        step_ms_min=min(steps_ms), step_ms_max=max(steps_ms),
        first_step=cmp, loss_rel_err=loss_err, loss_rtol=reference.LOSS_RTOL,
        grad_norm_rel_err=norm_err, grad_norm_rtol=reference.GRAD_NORM_RTOL,
        first_loss_is_the_compared_pass=same_pass,
        programs_loaded_or_compiled_in_window=loads,
        xla_compiles_in_window=compiles,
        model_flops_per_step=flops, tokens_per_step=mix["batch_size"]
        * mix["seq_len"],
        compile_cache=cache_dir, compile_cache_bytes=dir_bytes(cache_dir),
        last_loss=state["losses"][-1],
        setup_parts_s=dict(
            imports_and_config=t_launch - ctx.t0,
            build_profile_allocate=state["t_before_run"] - t_launch,
            preflight=state["t_first_iter"] - state["t_before_run"],
            first_step_vs_reference=state["t_compared"]
            - state["t_first_iter"],
            discarded_steps=state["t_open"] - state["t_compared"],
        ),
    )
    return dict(
        kind="train", correct=correct,
        attempted=len(steps_ms), failed=failed,
        setup_s=state["t_open"] - ctx.t0,
        window_s=stamps[-1] - stamps[0],
        steps=len(steps_ms), step_ms=steps_ms,
        dispatch_s=state["dispatch_s"],
        program_dispatches=state["program_dispatches"],
        model_flops_per_step=flops,
        trace=reduced,
    )
