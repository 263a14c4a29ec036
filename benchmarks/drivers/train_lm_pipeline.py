"""Driver: causal-LM training through ``experiment.launch.run``.

The same path and the same hook contract as ``train_pipeline.py`` (config
-> ParameterServer -> profilers -> ``Allocator`` -> ``PipelineModel`` ->
``Runner.train``; ONE hook compares the first step with the plain
reference in set-up, discards a few steps, stamps every iteration, stops
the runner), for a family whose launch config the configuration file
names and whose reference is ``benchmarks/reference/<family>.py``.

What ``correct`` compares, at the timed sizes, on the chip, all of it read
from the timed programs themselves:
  - the first step's loss, and the gradient norm of every pipeline stage,
    as the timed path computed them (``PipelineModel.compute_gradients``
    on the first batch), against the float32 reference over the same
    parameter arrays, a microbatch at a time;
  - the two mechanisms the configuration states a precision for beside
    the matrix products, in EVERY layer that has one: the stage programs
    sow what the router was handed and what it chose, what the scan was
    handed and the state it ended in (``PipelineModel.last_sown``: the
    compared pass's last microbatch), and the reference's router and
    literal recurrence run over those same inputs.  A layer that gives no
    reading fails the run;
  - the first UPDATE: the runner's first iteration is the compared pass
    again, with the optimizer's step; each parameter leaf's change is held
    against ``optax.adamw`` applied to the REFERENCE's gradients (relative
    norm of the difference, the worst leaf; a state left unchanged reads
    1).  The parameters before the step and the expected changes wait on
    the host meanwhile: the chip has no room for a second copy;
  - ``dropped_tokens == 0`` (counted from the rows the grouped products
    wrote); nothing compiled or loaded in the window; every loss finite;
    the first warm-up loss IS the compared pass.

``setup_s`` leaves the comparison's own seconds out (they are on the
``train_window`` line as ``setup_parts_s.comparison``): its programs are
kept out of the persistent compile cache, so they compile in every run,
and that minute is the benchmark's, not the program's.
"""

from __future__ import annotations

import contextlib
import importlib
import json
import math
import os
import time

from ..harness import nemotron_h_counts as counts

SCOPES = ("ssd_scan", "moe_route", "moe_experts", "shared_expert",
          "gqa_attn")


@contextlib.contextmanager
def _not_persisted():
    """Programs compiled in here stay out of the persistent compile cache.

    The comparison's own programs (the float32 reference a layer kind, the
    norms, the mechanism checks, the expected update) are 15.5 MB of
    executables that one pass of set-up uses once.  The chip machine's
    cache is capped (192 MiB), the cells of the benchmark cycle through it,
    and what one cell adds over the cap another cell recompiles on its next
    run: so the benchmark keeps its own programs out and pays their compile
    (about a minute) in every run, outside ``setup_s``.  The program's
    programs (stages, updates) are compiled outside and cached as ever."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _rel_err(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def _register_hook(reference, config):
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
    # the optimizer the configuration states, built here from optax (not
    # taken from the program)
    if config["training"]["optimizer"] != "adamw":
        raise ValueError("the first step's update is compared for adamw")
    first_step = optax.adamw(config["training"]["learning_rate"])

    @jax.jit
    def change_error(after, before, expected):
        """A leaf: (|(after - before) - expected| / |expected| (L2), share
        of its elements that moved AGAINST the expected change)."""
        def leaf(a, b, e):
            apart = jnp.linalg.norm(((a - b) - e).ravel())
            return (apart / jnp.maximum(jnp.linalg.norm(e.ravel()), 1e-30),
                    jnp.mean((a - b) * e < 0))
        return jax.tree_util.tree_map(leaf, after, before, expected)

    @HOOKS.register_module
    class BenchTrainLmHook(Hook):
        def __init__(self, state):
            self.s = state
            self.ctx = state["ctx"]
            self._mark = None

        # -- set-up: the first step against the plain reference ---------
        def _compare_first_step(self, runner):
            model = runner.model
            M = model.num_microbatches
            data, labels = runner.current_batch
            step_rng = jax.random.split(
                jax.random.wrap_key_data(jnp.asarray(runner.snapshot_rng()))
            )[1]
            grads, losses, _ = model.compute_gradients(data, labels, step_rng)
            pipe_loss = float(sum(jax.device_get(l) for l in losses))
            with _not_persisted():
                stage_sq = [float(sq_norm(g)) for g in grads]
                del grads, losses  # reduced to their norms and freed
                self._memory("first_step_computed")
                t0 = time.perf_counter()
                # the stages' own float32 parameter arrays: no copy
                params = [s.params for s in model.stages]
                # what the pass's last forward sowed, before anything runs
                # the stage programs again
                mechanisms = _check_mechanisms(
                    reference, config, [p for s in params for p in s],
                    model.last_sown())
                t1 = time.perf_counter()
                step = reference.make_reference_step(config, M)
                total = jax.tree_util.tree_map(jnp.zeros_like, params)
                ref_loss = 0.0
                for ids in _split_microbatches(np.asarray(data[0]), M):
                    loss, total = step(params, total, ids)
                    ref_loss += float(loss)
                ref_sq = [float(sq_norm(g)) for g in total]
                self._memory("reference_done")
                t2 = time.perf_counter()
                # the change the optimizer's first step should make, from
                # the reference's gradients, a layer at a time; it and the
                # parameters as they are wait on the host for that step
                expected = jax.jit(lambda g, p: first_step.update(
                    g, first_step.init(p), p)[0])
                flat = lambda by_stage: [x for s in by_stage for x in s]
                self._expected_change, self._params_before = [], []
                for g, p in zip(flat(total), flat(params)):
                    self._expected_change.append(
                        jax.device_get(expected(g, p)))
                    self._params_before.append(jax.device_get(p))
                del total
            self.s["compare"] = dict(
                pipeline_loss=pipe_loss, reference_loss=ref_loss,
                pipeline_stage_grad_norm=[math.sqrt(x) for x in stage_sq],
                reference_stage_grad_norm=[math.sqrt(x) for x in ref_sq],
                mechanisms_s=t1 - t0, reference_s=t2 - t1,
                expected_update_s=time.perf_counter() - t2, **mechanisms,
            )

        def _compare_update(self, runner):
            """After the runner's first iteration (the compared pass with
            the optimizer's step): every leaf's change against the one
            expected, a layer at a time."""
            t0 = time.perf_counter()
            after = [p for s in runner.model.stages for p in s.params]
            worst, where, against, by_layer = 0.0, None, None, []
            pair = lambda x: isinstance(x, tuple)
            with _not_persisted():
                for i, p1 in enumerate(after):
                    errs = jax.device_get(change_error(
                        p1, self._params_before[i],
                        self._expected_change[i]))
                    self._params_before[i] = self._expected_change[i] = None
                    leaves = jax.tree_util.tree_flatten_with_path(
                        errs, is_leaf=pair)[0]
                    by_layer.append(max(float(err) for _, (err, _) in leaves))
                    for path, (err, flipped) in leaves:
                        if float(err) >= worst:
                            worst, against = float(err), float(flipped)
                            where = f"layer {i}: " + "/".join(
                                str(getattr(k, "key", k)) for k in path)
            self.s["compare"].update(
                update_rel_err_worst_leaf=worst, update_worst_leaf=where,
                # Adam's first step is the gradient's sign: the reading is
                # 2 * sqrt(this share) where nothing else is apart
                update_worst_leaf_moved_against=against,
                update_rel_err_by_layer=by_layer,
                update_s=time.perf_counter() - t0)

        def _memory(self, label):
            stats = self.ctx.devices[0].memory_stats() or {}
            self.s["memory_bytes"][label] = dict(
                in_use=stats.get("bytes_in_use"),
                peak=stats.get("peak_bytes_in_use"),
                limit=stats.get("bytes_limit"))

        def before_run(self, runner):
            self._memory("stages_built")
            self.s["t_before_run"] = time.perf_counter()
            self.s["stage_layers"] = [
                s.num_layers for s in runner.model.stages
            ]
            self.s["stage_units"] = [
                [cfg.get("mixer") or cfg["layer_type"] for cfg in
                 json.loads(s.config_key)] for s in runner.model.stages
            ]
            self.s["programs_a_stage"] = [
                len(getattr(s, "layers", [s])) for s in runner.model.stages
            ]
            self.s["params"] = sum(
                int(np.prod(a.shape)) for s in runner.model.stages
                for a in jax.tree_util.tree_leaves(s.params))

        def before_train_iter(self, runner):
            if runner.iter == 0:
                self.s["t_first_iter"] = time.perf_counter()
                self._compare_first_step(runner)
                self.s["t_compared"] = time.perf_counter()
                self.s["comparison_s"] = (self.s["t_compared"]
                                          - self.s["t_first_iter"])
            self._mark = self.ctx.tracer.mark()
            self._mark.__enter__()

        def _counters(self, runner):
            read = getattr(runner.model, "read_counters", None)
            return read() if read else {}

        def after_train_iter(self, runner):
            self._mark.__exit__(None, None, None)
            now = time.perf_counter()
            s, stats = self.s, runner.model.stats
            if s["t_open"] is None:
                s["warm_losses"].append(float(stats.loss))
                if len(s["warm_losses"]) == 1:
                    self._compare_update(runner)
                    s["comparison_s"] += s["compare"]["update_s"]
                if runner.iter >= s["discard"]:
                    # one read of the device's counters, before the window
                    s["counters_open"] = self._counters(runner)
                    self._memory("window_opens")
                    s["loads_open"] = self.ctx.loads.count
                    s["compiles_open"] = xla_compile_count()
                    now = time.perf_counter()
                    s["t_open"] = now
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
                # ... and one after it
                s["counters_close"] = self._counters(runner)
                self._memory("window_closed")
                if self.ctx.tracer.state == "done":
                    scoped = getattr(runner.model, "scoped_instructions",
                                     None)
                    s["scoped_instructions"] = (
                        scoped(SCOPES) if scoped else None)
                runner.request_stop()
            else:
                self.ctx.tracer.poll(now)


def _check_mechanisms(reference, config, params, sown) -> dict:
    """The router's choices and the scan's final state of EVERY layer that
    has one, as the stage programs sowed them, against the reference's
    router and literal recurrence over the inputs sown beside them (the
    program's own, in its compute dtype, widened exactly).  ``params`` and
    ``sown``: a layer an entry, in layer order."""
    import jax
    import jax.numpy as jnp

    c = reference._config_view(config)
    f32 = lambda tree: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), tree)

    @jax.jit
    def scan_error(mixer, s):
        with jax.default_matmul_precision("highest"):
            s = f32(s)
            _, state = reference.literal_scan(
                s["x"], s["dt"], -jnp.exp(mixer["A_log"]), s["B"], s["C"],
                mixer["D"])
        return jnp.sqrt(jnp.sum((s["state"] - state) ** 2)
                        / jnp.sum(state ** 2))

    @jax.jit
    def choices_apart(mixer, s):
        with jax.default_matmul_precision("highest"):
            idx, _ = reference.route(f32(mixer), f32(s["tokens"]), c)
        # share of the program's (token, choice) pairs the reference did
        # not choose
        return 1.0 - jnp.mean(
            (s["idx"][:, :, None] == idx[:, None, :]).any(axis=-1))

    scan, route = [], []
    for p, s in zip(params, sown):
        if "last_scan" in s:
            scan.append(float(scan_error(p["mixer"], s["last_scan"])))
        if "last_route" in s:
            route.append(float(choices_apart(p["mixer"], s["last_route"])))
    return dict(scan_state_rel_l2=scan, router_choices_apart=route)


def run(ctx) -> dict:
    config, mix = ctx.config, ctx.traffic
    reference = importlib.import_module(
        f"benchmarks.reference.{config['family']}")
    log_root = os.path.join(ctx.out_dir, "logs")
    model_keys = {k: v for k, v in config.items()
                  if isinstance(v, (int, float, str, bool))}
    json_path = os.path.join(ctx.out_dir, "model_keys.json")
    with open(json_path, "w") as fh:
        json.dump(model_keys, fh)
    os.environ.update(
        SKYTPU_NEMOTRON_JSON=json_path,
        SKYTPU_CORE_NUM=str(mix["stages"]),
        SKYTPU_MICROBATCHES=str(mix["microbatches"]),
        SKYTPU_BATCH_SIZE=str(mix["batch_size"]),
        SKYTPU_SEQ_LEN=str(mix["seq_len"]),
        SKYTPU_ALLOCATE_TYPE=mix["allocate_type"],
        SKYTPU_SCHEDULE=mix["schedule"],
        SKYTPU_OPTIM=config["training"]["optimizer"],
        SKYTPU_LR=str(config["training"]["learning_rate"]),
        SKYTPU_DATA_SEED=str(ctx.seed),   # --seed draws the token ids
        SKYTPU_MAX_ITERS=str(10 ** 9),
        SKYTPU_MAX_EPOCHS=str(10 ** 9),
        SKYTPU_LOG_ROOT=log_root,
    )
    os.environ.pop("STIMULATE", None)

    from experiment.launch import run as launch_run
    from skycomputing_tpu import load_config
    from skycomputing_tpu.utils import Logger, compilation_cache_dir
    from ..harness.runtime import dir_bytes
    from ..harness.stats import median, percentile

    cfg = load_config(os.path.join(ctx.root, config["launch"]["config"]))
    if ctx.rehearse:
        # the engine runs a program a layer from a mean layer size up; a
        # rehearsal's widths are tiny, so its line is too: the walk goes
        # the way the chip's does
        from skycomputing_tpu.parallel import pipeline
        pipeline.LAYER_PROGRAM_MIN_BYTES = 0
    _register_hook(reference, config)
    state = dict(
        ctx=ctx, discard=int(mix["discard_steps"]), t_open=None,
        warm_losses=[], stamps=[], losses=[], dispatch_s=[],
        program_dispatches=[], scoped_instructions=None, memory_bytes={},
    )
    cfg.train_config["hook_config"].append(
        dict(type="BenchTrainLmHook", state=state)
    )
    cache_before = dir_bytes(os.environ.get("JAX_COMPILATION_CACHE_DIR")
                             or os.path.join(ctx.root, ".jax_cache"))
    t_launch = time.perf_counter()
    rc = launch_run(cfg, Logger(**cfg.logging_config))
    if rc != 0 or not state["stamps"]:
        raise RuntimeError(
            f"experiment.launch.run returned {rc} with "
            f"{len(state['stamps'])} steps in the window "
            f"(see {cfg.logging_config['filename']})"
        )

    reduced = ctx.tracer.reduce()  # outside the window
    cache_dir = compilation_cache_dir()
    cmp = state["compare"]
    loss_err = _rel_err(cmp["pipeline_loss"], cmp["reference_loss"])
    norm_errs = [_rel_err(a, b) for a, b in zip(
        cmp["pipeline_stage_grad_norm"], cmp["reference_stage_grad_norm"])]
    loads = state["loads_close"] - state["loads_open"]
    compiles = state["compiles_close"] - state["compiles_open"]
    failed = sum(1 for x in state["losses"] if not math.isfinite(x))
    same_pass = state["warm_losses"][0] == cmp["pipeline_loss"]

    stamps = [state["t_open"]] + state["stamps"]
    steps_ms = [(b - a) * 1e3 for a, b in zip(stamps, stamps[1:])]
    steps = len(steps_ms)

    # the window's own routing: the device's totals after, less before
    opened, closed = state["counters_open"], state["counters_close"]
    expert_tokens, routed, dropped, skew = None, None, 0, None
    if closed:
        expert_tokens = [
            [int(b) - int(a) for a, b in zip(before, after)]
            for before, after in zip(opened["expert_tokens"],
                                     closed["expert_tokens"])]
        routed = closed["tokens_routed_here"] - opened["tokens_routed_here"]
        dropped = closed["dropped_tokens"]  # since the stages were built
        skew = max(max(t) / (sum(t) / len(t)) for t in expert_tokens)
    e_layers = max(config["hybrid_override_pattern"].count("E"), 1)
    # (token, held expert) pairs a sequence brings an expert layer: what
    # the window really routed here, or (a rehearsal's short window) the
    # uniform expectation
    uniform = mix["seq_len"] * config["num_experts_per_tok"] \
        * config["n_routed_experts"] / config.get(
            "n_routed_experts_published", config["n_routed_experts"])
    pairs = routed / (steps * mix["batch_size"] * e_layers) \
        if routed else uniform
    flops = counts.train_step_flops(
        config, batch=mix["batch_size"], seq=mix["seq_len"],
        pairs_a_layer=pairs)

    # every M layer a scan reading, every E layer a router reading: one
    # that is missing fails
    pattern = config["hybrid_override_pattern"]
    scan_errs, apart = cmp["scan_state_rel_l2"], cmp["router_choices_apart"]
    mechanisms_read = (len(scan_errs) == pattern.count("M")
                       and len(apart) == pattern.count("E"))
    correct = (
        loss_err <= reference.LOSS_RTOL
        and max(norm_errs) <= reference.GRAD_NORM_RTOL
        and mechanisms_read
        and max(apart, default=0.0) <= reference.ROUTER_CHOICE_MISMATCH
        and max(scan_errs, default=0.0) <= reference.SCAN_STATE_RTOL
        and cmp["update_rel_err_worst_leaf"] <= reference.UPDATE_RTOL
        and (bool(closed) or "E" not in pattern) and dropped == 0
        and same_pass and failed == 0 and loads == 0 and compiles == 0
    )
    ctx.emit(
        event="train_window",
        stage_layer_units=state["stage_layers"],
        stage_units=state["stage_units"], parameters=state["params"],
        programs_a_stage=state["programs_a_stage"],
        steps=steps, window_s=stamps[-1] - stamps[0],
        step_ms_median=median(steps_ms), step_ms_p95=percentile(steps_ms, 95),
        step_ms_min=min(steps_ms), step_ms_max=max(steps_ms),
        first_step=cmp, loss_rel_err=loss_err, loss_rtol=reference.LOSS_RTOL,
        stage_grad_norm_rel_err=norm_errs,
        grad_norm_rtol=reference.GRAD_NORM_RTOL,
        router_choices_apart_limit=reference.ROUTER_CHOICE_MISMATCH,
        scan_state_rtol=reference.SCAN_STATE_RTOL,
        update_rtol=reference.UPDATE_RTOL,
        every_mechanism_read=mechanisms_read,
        first_loss_is_the_compared_pass=same_pass,
        programs_loaded_or_compiled_in_window=loads,
        xla_compiles_in_window=compiles,
        expert_tokens_in_window=expert_tokens,
        tokens_routed_here_in_window=routed, dropped_tokens=dropped,
        pairs_a_sequence_a_layer=pairs,
        model_flops_per_step=flops,
        tokens_per_step=mix["batch_size"] * mix["seq_len"],
        compile_cache=cache_dir, compile_cache_bytes=dir_bytes(cache_dir),
        compile_cache_bytes_before=cache_before,
        last_loss=state["losses"][-1], memory_bytes=state["memory_bytes"],
        setup_parts_s=dict(
            imports_and_config=t_launch - ctx.t0,
            build_profile_allocate=state["t_before_run"] - t_launch,
            preflight=state["t_first_iter"] - state["t_before_run"],
            comparison=state["comparison_s"],
            discarded_steps=state["t_open"] - state["t_compared"]
            - cmp["update_s"],
        ),
    )
    return dict(
        kind="train", correct=correct,
        attempted=steps, failed=failed,
        # the comparison is the benchmark's own work (module docstring)
        setup_s=state["t_open"] - ctx.t0 - state["comparison_s"],
        window_s=stamps[-1] - stamps[0],
        steps=steps, step_ms=steps_ms,
        dispatch_s=state["dispatch_s"],
        program_dispatches=state["program_dispatches"],
        model_flops_per_step=flops,
        trace=reduced,
        # what the new per-layer readers read
        config=config, traffic=mix,
        programs_a_stage=state["programs_a_stage"],
        expert_load_max_over_mean=skew,
        pairs_a_sequence_a_layer=pairs,
        scoped_instructions=state["scoped_instructions"],
    )
