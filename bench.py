#!/usr/bin/env python
"""Headline benchmark: optimal-vs-even allocation speedup.

Reproduces the reference's headline experiment (README.md:5 — "55% training
time improvement" for profiled MIP allocation vs even allocation on a
heterogeneous cluster).  Heterogeneity is injected exactly as the reference
injects it on homogeneous hardware: per-worker compute slowdown factors
drawn from the reference experiment's own generator (integers in [1, 7),
seed 35 — ``/root/reference/experiment/config.py:67-71``) plus the seeded
Stimulator's memory skew, applied both to the profiles the allocator sees
and to the emulated runtime stage times.

The memory regime defaults to the reference experiment's: every worker ran
with ``mem_limit=-1`` (probe real free device memory,
``/root/reference/experiment/config.py:86``) on 16 GB-class nodes, so
memory constrains feasibility but compute heterogeneity binds the
allocation.  See ``skycomputing_tpu/dynamics/headline.py`` — the CI guard
(`tests/test_headline_metric.py`) builds its instance through the same
module, so guard and bench can never drift apart again.

Method (single chip or many):
1. profile + allocate with ``even`` and ``optimal`` strategies;
2. build the real pipeline for each and **measure true per-stage
   forward+backward wall times on the TPU** (compiled, blocked, median of
   repeats);
3. emulated heterogeneous stage time = measured_time x worker_slowdown;
4. step time under the engine's microbatched GPipe schedule with M
   microbatches:  t_step = sum_k tau_k / M + (M-1)/M * max_k tau_k
   (fill-drain + steady state paced by the bottleneck stage);
5. also executes one real train step per allocation as an end-to-end sanity
   check (loss must be finite).

The metric is the step-time improvement of optimal over even; vs_baseline
divides by the reference's published 55%.

Device contract
---------------
The measurement is of a TPU, so the script needs one: with any other
backend it exits non-zero before measuring and prints no result line.
One process holds the chip — nothing here starts a child that needs it.

Wall-clock budget
-----------------
``SKYTPU_BENCH_DEADLINE_S`` (default 1680 s) is the wall budget: refine
iterations, the final re-measurement, and the ffn/1 side number each run
only if the remaining budget affords them (estimated from the measured
duration of the previous pass), and the skipped ones are listed in
``phases_skipped``.  SIGTERM/SIGALRM print the best-so-far JSON line
(with a ``partial`` provenance field) and exit non-zero, as does a
crash: what a truncated run prints is evidence, never a pass.

Prints one JSON line with machine-readable provenance:
    {"metric": ..., "value": ..., "unit": "percent", "vs_baseline": ...,
     "platform": "tpu", "device_kind": ..., "partial": absent | "..."}

Env knobs: SKYTPU_BENCH_WORKERS (64), SKYTPU_BENCH_LAYER_NUM (53 trios ->
the paper's 160-layer scale), SKYTPU_BENCH_PRESET (large),
SKYTPU_BENCH_BATCH (32), SKYTPU_BENCH_MICROBATCHES (4x workers),
SKYTPU_BENCH_SLOWDOWN (paper | stimulator), SKYTPU_BENCH_REPEATS (4),
SKYTPU_BENCH_MEM_REGIME (reference | tight), SKYTPU_BENCH_MEM_MB
(numeric override of the raw per-worker budget),
SKYTPU_BENCH_DEADLINE_S (1680), SKYTPU_BENCH_SOLVER_S (adaptive <=90),
SKYTPU_BENCH_POLISH (6 measured-time bottleneck boundary
moves), SKYTPU_BENCH_REFINE (0 — the affine first solve is the
fixed point; deadline-gated when enabled), SKYTPU_BENCH_EVEN_BRACKET (1),
SKYTPU_BENCH_CALIBRATION (types | affine | scale | 0),
SKYTPU_BENCH_SEQUENTIAL=1 to score the reference's non-microbatched
schedule (sum of stage times) instead.  The persistent XLA compile cache
lives where utils/compile_cache.py says (JAX_COMPILATION_CACHE_DIR, else
<checkout>/.jax_cache; SKYTPU_COMPILE_CACHE=0 turns it off);
SKYTPU_HOTPATH=0 restores the legacy per-microbatch dispatch path of the
pipeline engine (A/B for tools/bench_step_overhead.py).
"""

from __future__ import annotations

import json
import os
import signal
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# The bench's ~6 successive 64-stage allocations hold >64 distinct slice
# structures; the library's default cache would evict programs the very
# next pass re-compiles (r04's wall-clock blowup).  Set before the
# package import so the module-level cap picks it up.
os.environ.setdefault("SKYTPU_PROGRAM_CACHE_MAX", "256")

# 1680 s = 28 min: the alarm backstop fires at deadline+60 s, and every
# pass is gated so the normal path finishes well before.
_T0 = time.time()
_DEADLINE_S = float(os.getenv("SKYTPU_BENCH_DEADLINE_S", "1680"))


def _elapsed() -> float:
    return time.time() - _T0


def _time_left() -> float:
    return _DEADLINE_S - _elapsed()


# Best-so-far result, updated in place as passes complete; the signal
# handlers and the normal exit path both print it exactly once.
_RESULT = {
    "metric": None,
    "value": None,
    "unit": "percent",
    "vs_baseline": None,
    "partial": "startup: no measurement completed yet",
}
_EMITTED = False

# Certification phases the deadline gates forced us to skip or truncate
# ("polish", "final_remeasure", "refine", "even_bracket", "ffn1").  Always
# present in the JSON record — an empty list is the positive statement
# that every enabled phase ran to completion, so a reader can tell
# "polish converged at 0 moves" from "polish never got budget" (the r05
# record conflated exactly those two).
_PHASES_SKIPPED: list = []
_RESULT["phases_skipped"] = _PHASES_SKIPPED


def _skip_phase(name: str) -> None:
    if name not in _PHASES_SKIPPED:
        _PHASES_SKIPPED.append(name)


def _emit() -> None:
    global _EMITTED
    if _EMITTED:
        return
    _EMITTED = True
    out = {k: v for k, v in _RESULT.items() if k != "partial" or v}
    out["elapsed_s"] = round(_elapsed(), 1)
    out["deadline_s"] = _DEADLINE_S
    print(json.dumps(out), flush=True)


def _on_signal(signum, frame):
    _RESULT.setdefault("partial", None)
    if not _RESULT.get("partial"):
        _RESULT["partial"] = f"killed by signal {signum}"
    else:
        _RESULT["partial"] = (
            f"{_RESULT['partial']}; killed by signal {signum}"
        )
    _emit()
    os._exit(1)


signal.signal(signal.SIGTERM, _on_signal)
signal.signal(signal.SIGALRM, _on_signal)
# hard backstop: if the deadline-aware logic miscalculates (e.g. one XLA
# compile blows past its estimate), SIGALRM still emits best-so-far with
# a little grace for the driver's own timeout margin
signal.alarm(max(int(_time_left()) + 60, 60))


import jax

if jax.devices()[0].platform != "tpu":
    # before anything is built or measured, and with no result line: a
    # run without the chip has nothing to report
    sys.exit(
        f"bench.py measures a TPU and found "
        f"{jax.devices()[0].platform!r} ({jax.devices()[0].device_kind}); "
        f"no result"
    )

import numpy as np
import optax

from skycomputing_tpu.utils import enable_persistent_compilation_cache

# Persistent XLA compile cache: repeated runs on the chip stop re-paying
# the stage-program compile bill.  Placement is utils/compile_cache.py's;
# the active dir ships in the JSON record.
_COMPILE_CACHE_DIR = enable_persistent_compilation_cache()


def main() -> int:
    from skycomputing_tpu.dataset import (
        RandomTensorGenerator,
        RandomTokenGenerator,
    )
    from skycomputing_tpu.dynamics import (
        Allocator,
        DeviceBenchmarker,
        ModelBenchmarker,
        ParameterServer,
        WorkerManager,
    )
    from skycomputing_tpu.dynamics.headline import (
        schedule_step_time,
        worker_mem_budget_mb,
        worker_slowdowns,
    )
    from skycomputing_tpu.models import bert_config, bert_layer_configs
    from skycomputing_tpu.ops import cross_entropy_loss
    from skycomputing_tpu.parallel import PipelineModel

    # defaults reproduce the paper's headline scale: 160-layer stacked
    # BERT-large (53 trios + ends = 162 units) over 64 heterogeneous
    # workers, GPipe with 2 microbatches per worker
    n_workers = int(os.getenv("SKYTPU_BENCH_WORKERS", "64"))
    layer_num = int(os.getenv("SKYTPU_BENCH_LAYER_NUM", "53"))
    preset = os.getenv("SKYTPU_BENCH_PRESET", "large")
    batch = int(os.getenv("SKYTPU_BENCH_BATCH", "32"))
    # M = 4 x stages: the GPipe-standard minimum for an acceptable bubble
    # fraction ((S-1)/(M+S-1) = 33% at M=2S vs 20% at 4S) — a 64-stage
    # deployment would not run shallower.  Each microbatch is one measured
    # batch; M microbatches = the global training batch.
    n_micro = int(os.getenv("SKYTPU_BENCH_MICROBATCHES", str(4 * n_workers)))
    slowdown_kind = os.getenv("SKYTPU_BENCH_SLOWDOWN", "paper")
    sequential = os.getenv("SKYTPU_BENCH_SEQUENTIAL") == "1"
    repeats = int(os.getenv("SKYTPU_BENCH_REPEATS", "4"))
    mem_regime = os.getenv("SKYTPU_BENCH_MEM_REGIME", "reference")
    # allocation granularity: FFN up-projections split into this many
    # column-shard units (numerically identical model, see
    # models/bert.py::BertLayer_BodyShard).  The reference's fixed
    # 1/3-encoder granularity leaves the chunky FFN unit pinning the
    # achievable bottleneck on heterogeneous clusters; finer units are a
    # capability of this framework's allocator, so the headline runs with
    # them (SKYTPU_BENCH_FFN_SHARDS=1 restores reference granularity).
    ffn_shards = int(os.getenv("SKYTPU_BENCH_FFN_SHARDS", "2"))
    seq = 128

    def note(msg: str) -> None:
        print(
            f"# [{time.strftime('%H:%M:%S')}] [{_time_left():.0f}s left] "
            f"{msg}",
            file=sys.stderr, flush=True,
        )

    devices = jax.devices()
    note(f"backend up: {devices}")
    cfg = bert_config(preset, hidden_dropout_prob=0.0,
                      attention_probs_dropout_prob=0.0)
    model_cfg = bert_layer_configs(
        cfg, num_encoder_units=layer_num, num_classes=3, deterministic=True,
        ffn_shards=ffn_shards,
    )
    mode = "sequential" if sequential else f"GPipe-M{n_micro}"
    _RESULT.update(
        metric=(
            f"{len(model_cfg)}-unit stacked BERT-{preset} "
            f"({layer_num} encoder layers, ffn/{ffn_shards}) "
            f"{mode} step-time improvement, optimal vs even "
            f"allocation, {n_workers} heterogeneous workers "
            f"({slowdown_kind} slowdowns, {mem_regime} memory "
            f"regime), measured on {devices[0].device_kind}"
        ),
        platform=devices[0].platform,
        device_kind=devices[0].device_kind,
    )

    slowdowns = worker_slowdowns(n_workers, slowdown_kind)
    from skycomputing_tpu.stimulator import Stimulator

    mem_skew = np.asarray(Stimulator(n_workers).m_slowdown[:n_workers])

    rng = np.random.default_rng(0)
    ids = rng.integers(5, cfg.vocab_size, size=(batch, seq)).astype(np.int32)
    types = np.zeros_like(ids)
    mask = np.ones_like(ids)
    labels = rng.integers(0, 3, size=(batch,)).astype(np.int32)
    data = (ids, types, mask)

    ps = ParameterServer(model_cfg, example_inputs=data, rng=jax.random.key(0))
    # ONE optimizer object for every measurement pass: the stage-program
    # cache keys on (slice structure, id(optimizer)), so a fresh optax
    # object per pass would defeat cross-pass reuse of compiled programs —
    # exactly the r04 wall-time blowup (VERDICT r04 task #2)
    optimizer = optax.sgd(1e-3)

    # one ModelBenchmarker shared by both allocations (config-hash cached)
    # — its profile also feeds the memory-budget helper.  Default profile
    # is TIMED (measured per-unit fwd+bwd seconds): static FLOPs mis-rank
    # memory-bound attention thirds vs matmul-bound FFN thirds, and the
    # allocator can only optimize the bottleneck it can see
    # (SKYTPU_BENCH_PROFILE=static restores the abstract-shapes profile).
    profile_kind = os.getenv("SKYTPU_BENCH_PROFILE", "timed")
    model_bench = ModelBenchmarker(
        model_cfg,
        RandomTokenGenerator(batch_size=batch, seq_length=seq,
                             vocab_size=cfg.vocab_size),
        timed=(profile_kind == "timed"),
    )
    note(f"model profile ({profile_kind})...")
    t_prof0 = time.time()
    _, layer_mem = model_bench.benchmark()
    profile_s = time.time() - t_prof0
    note(f"model profile done in {profile_s:.0f}s: {len(layer_mem)} layers, "
         f"{sum(layer_mem) / 1024:.1f} GB total estimate")
    # raw per-worker budget per the chosen regime (default: the reference's
    # loose mem_limit=-1 probe world — see dynamics/headline.py); worker
    # capacity_i = budget / mem_skew_i, applied once by ProfileSkew below
    mem_env = os.getenv("SKYTPU_BENCH_MEM_MB")
    if mem_env is not None:
        mem_budget_mb = float(mem_env)
    else:
        mem_budget_mb = worker_mem_budget_mb(layer_mem, n_workers, mem_regime)
    note(f"memory regime {mem_regime!r}: raw per-worker budget "
         f"{mem_budget_mb:.0f} MB")

    class ProfileSkew:
        """Stimulator-compatible hook feeding the chosen slowdown draw."""

        def compute_slowdown(self, rank):
            return float(slowdowns[rank])

        def memory_slowdown(self, rank):
            return float(mem_skew[rank])

    last_pass_s = [0.0]  # duration of the most recent measurement pass
    full_pass_s = [0.0]  # duration of the last UNSEEDED (full) pass
    # every timing sample chains 3 executions before one block, so host
    # dispatch latency amortizes out of the per-stage device time
    inner_iters = 3

    def solver_budget() -> float:
        """Anneal wall budget for one solve: bounded so the (1-core)
        escalating anneal can never eat the measurement passes' time —
        r04's default 300 s cap overshot to 347 s on this instance."""
        return float(
            os.getenv("SKYTPU_BENCH_SOLVER_S",
                      str(min(90.0, max(10.0, _time_left() * 0.06))))
        )

    def measure_current_allocation(wm, label, ps, n_repeats=None,
                                   sanity=True, seed_times=None):
        """Build the real pipeline for the CURRENT allocation, optionally
        sanity-train one step, measure raw per-stage times, and score the
        emulated heterogeneous step time.  Worker slowdown fields are
        zeroed only for the duration of the measurement (the schedule
        model applies them to the measured times), then restored so a
        later re-allocation still sees the heterogeneity config."""
        t_pass0 = time.time()
        was_seeded = bool(seed_times)
        saved = {}
        stage_slowdowns = []
        for w in sorted(wm.worker_pool, key=lambda w: w.rank):
            if w.model_config:
                stage_slowdowns.append(float(w.extra_config["slowdown"]))
            saved[id(w)] = w.extra_config.get("slowdown", 1.0)
            w.extra_config["slowdown"] = 1.0
        loss = None
        try:
            # pre-flight plan verification (abstract, eval_shape only):
            # a malformed allocation is rejected HERE with a precise
            # diagnostic, before the pipeline build pays any compile.
            # Memory surfaces as warnings — the even baseline ignores
            # budgets by design and the allocator already enforced them
            # for the optimal side.
            from skycomputing_tpu.analysis.plan_check import verify_plan

            plan_report = verify_plan(
                model_cfg, wm, data, layer_mem=layer_mem, memory="warn"
            )
            for issue in plan_report.issues:
                note(f"{label}: pre-flight {issue.format()}")
            plan_report.raise_if_failed()
            note(f"{label}: pre-flight {plan_report.summary()}")
            model = PipelineModel(
                wm, ps, optimizer, cross_entropy_loss, devices=devices
            )
            if sanity:
                note(f"{label}: pipeline built ({len(model.stages)} "
                     f"stages); running one sanity train step...")
                # end-to-end sanity: the pipeline actually trains
                loss = model.train_step(data, labels, rng=jax.random.key(0))
                if not np.isfinite(loss):
                    raise RuntimeError(f"{label}: non-finite loss {loss}")
                note(f"{label}: train step ok; measuring per-stage times...")
            else:
                note(f"{label}: pipeline built ({len(model.stages)} "
                     f"stages); measuring per-stage times...")
            # pass wall time is dominated by the stage compiles, not the
            # timed loops — generous repeats are nearly free and shrink
            # the run-to-run noise that otherwise feeds the refine
            # calibration
            measured = model.measure_stage_times(
                data, repeats=n_repeats or repeats,
                inner_iters=inner_iters, seed_times=seed_times,
            )
        finally:
            for w in wm.worker_pool:
                w.extra_config["slowdown"] = saved[id(w)]
        taus = [t * s for t, s in zip(measured, stage_slowdowns)]
        step = schedule_step_time(taus, n_micro, sequential)
        loss_txt = f"{loss:.3f}" if loss is not None else "skipped"
        print(
            f"# {label}: step={step:.4f}s loss={loss_txt} layers="
            f"{[len(w.model_config) for w in sorted(wm.worker_pool, key=lambda w: w.rank)]} "
            f"measured={[round(t, 4) for t in measured]} "
            f"slowdowns={stage_slowdowns}",
            file=sys.stderr,
        )
        last_pass_s[0] = time.time() - t_pass0
        if not was_seeded:
            # a pass that started with no prior measurements is a FULL
            # pass — the budget gates size the final re-measurement from
            # it (an initially-empty seed dict counts: it was populated
            # by this pass, not consulted)
            full_pass_s[0] = last_pass_s[0]
        note(f"{label}: pass took {last_pass_s[0]:.0f}s")
        return step, measured

    def record_best(even_step, opt_step, gap, history, partial):
        """Refresh the best-so-far JSON fields after every optimal-side
        measurement, so a kill at any later point still reports a real
        (if less-refined) number."""
        speedup = (even_step - opt_step) / even_step * 100
        _RESULT.update(
            value=round(speedup, 2),
            vs_baseline=round(speedup / 55.0, 4),
            solver_gap=(
                round(gap, 4) if gap is not None and np.isfinite(gap)
                else None
            ),
            refine_steps=list(history),
            partial=partial,
        )

    # closed-loop refinement: measure -> recalibrate per-layer costs ->
    # re-solve (Allocator.refine_allocation), keeping the best emulated
    # step time.  0 disables.  Iterations run only while the wall budget
    # affords them (each costs ~one measurement pass).  Default 0 since
    # the affine even-pass calibration landed: across the r05 trials the
    # first solve IS the loop's fixed point (refine deltas +0.1%..+20%,
    # never negative — pure measurement noise re-solved into worse
    # allocations), so the passes go to lower-variance measurement
    # instead: symmetric repeats on both sides and the even drift
    # bracket below.  The closed loop remains available (env knob) and
    # CI-tested (tests/test_dynamics.py) for instances whose profiles
    # mispredict reality badly enough to need it.
    refine_iters = int(os.getenv("SKYTPU_BENCH_REFINE", "0"))
    # even-pass calibration mode (default "types"): one cost per
    # distinct unit CONFIG regressed from the even pass's measured stage
    # times — the only stochastic input is the stage-time medians, which
    # de-lotteries the solve (see the mode branch below).  "affine" fits
    # cost(slice) = a*sum(units) + b*|slice| on the timed per-unit
    # profile (r04 task #3); "scale" is the r04 uniform per-slice
    # rescale; "0" disables seeding entirely.  The JSON `calibration`
    # field carries {mode, costs} for types and {mode, a, b} for affine.
    calib_mode = os.getenv("SKYTPU_BENCH_CALIBRATION", "types")
    calib_fit = None

    step_times = {}
    solver_gap = None  # certified optimality gap of the optimal allocation
    refine_history = []
    final_remeasured = False
    for alloc_type in ("even", "optimal"):
        wm = WorkerManager()
        wm.load_worker_pool_from_config(
            [
                dict(
                    name=f"node-{i}",
                    device_config=dict(device_index=i % len(devices)),
                    # raw budget: the DeviceBenchmarker divides by the
                    # ProfileSkew memory_slowdown (skew applied exactly once)
                    extra_config=dict(
                        slowdown=float(slowdowns[i]),
                        mem_limit=mem_budget_mb,
                    ),
                )
                for i in range(n_workers)
            ]
        )
        allocator = Allocator(
            model_cfg,
            wm,
            model_bench,
            DeviceBenchmarker(
                wm,
                RandomTensorGenerator(size=(256, 1024)),
                [dict(layer_type="MatmulStack", features=1024, depth=4)],
                iterations=5,
                devices=devices,
                stimulator=ProfileSkew(),
            ),
        )
        note(f"{alloc_type}: profiling devices + allocating...")
        if alloc_type == "even":
            allocator.even_allocate()
            note(f"{alloc_type}: allocation done")
            step_times[alloc_type], even_measured = (
                measure_current_allocation(wm, alloc_type, ps,
                                           n_repeats=repeats + 2,
                                           sanity=False)
            )
            even_counts = [
                len(w.model_config)
                for w in sorted(wm.worker_pool, key=lambda w: w.rank)
                if w.model_config
            ]
            even_wm, even_pass_s = wm, last_pass_s[0]
            _RESULT["partial"] = (
                "even baseline measured; optimal pass did not complete"
            )
            continue

        def snapshot_allocation():
            return [
                (w, list(w.model_config or []), w.order, w.rank)
                for w in wm.worker_pool
            ]

        def restore_allocation(snap):
            for w, mc, order, rank in snap:
                w.model_config = mc
                w.order = order
                w.rank = rank

        if calib_mode == "types":
            # per-unit-TYPE costs regressed from the even pass alone:
            # the affine fit keeps the single-draw timed profile in its
            # feature, and its per-unit overhead estimate swung 0.009 ->
            # 0.106 across r05 trials — each swing re-rolls the solver's
            # allocation (the real headline lottery).  Stacked models
            # have ~6 distinct unit configs, so the even pass's measured
            # structures give a small well-posed regression whose only
            # stochastic input is the stage-time medians.
            note("optimal: per-type cost calibration from the even "
                 "baseline's measured stage times...")
            fit = allocator.calibrate_costs_by_type(
                even_counts, even_measured
            )
            calib_fit = {"mode": "types",
                         "costs": [round(v, 5) for v in
                                   sorted(fit.values(), reverse=True)]}
            note(f"optimal: fitted {len(fit)} type costs "
                 f"{calib_fit['costs']}")
        elif calib_mode == "affine":
            # seed the cost model from the even baseline's measured stage
            # times (already taken), slice-size-aware: the isolated-unit
            # profile misses per-unit overhead that only shows up inside
            # deployed slices, and a plain per-slice rescale learned at
            # even granularity transfers poorly to the solver's slices
            note("optimal: affine cost calibration from the even "
                 "baseline's measured stage times...")
            a, b = allocator.calibrate_costs_affine(
                even_counts, even_measured
            )
            calib_fit = {"mode": "affine", "a": a, "b": b}
            note(f"optimal: fitted cost(slice) = {a:.4g}*sum(units) + "
                 f"{b:.4g}*|slice|")
        elif calib_mode != "0":
            note("optimal: calibrating per-layer costs from the even "
                 "baseline's measured stage times (uniform rescale)...")
            allocator.calibrate_costs(even_counts, even_measured)
            calib_fit = {"mode": "scale"}
        t_solve0 = time.time()
        allocator.optimal_allocate(max_time=solver_budget())
        solve_s = time.time() - t_solve0
        solver_gap = allocator.last_result.optimality_gap
        note(f"{alloc_type}: allocation done")
        opt_seed = {}
        # repeats+2 = the even baseline's count: on paths where nothing
        # later re-measures (polish converges at 0 moves), this IS the
        # optimal side of the headline subtraction and must carry the
        # same noise level as the even side
        initial_step, measured = measure_current_allocation(
            wm, alloc_type, ps, n_repeats=repeats + 2,
            seed_times=opt_seed,
        )
        best_step, best_gap = initial_step, solver_gap
        best_snap = snapshot_allocation()
        refine_history.append(round(best_step, 4))
        record_best(step_times["even"], best_step, best_gap,
                    refine_history,
                    "initial optimal measured; refinement incomplete")
        ran_refines = 0
        for it in range(1, refine_iters + 1):
            # each refine costs ~one measurement pass (plus a cheap
            # re-solve); never start one the budget can't absorb while
            # still leaving room for the final re-measurement
            need = 0.6 * last_pass_s[0] + solve_s \
                + 0.45 * last_pass_s[0] + 60
            if _time_left() < need:
                note(f"refine stopped before iteration {it}: "
                     f"{_time_left():.0f}s left < {need:.0f}s needed")
                _skip_phase("refine")
                break
            # measured raw per-stage seconds calibrate the per-layer costs
            # (slice-level fusion/cache effects the per-unit profile cannot
            # see), then the solver re-runs on the calibrated instance
            note(f"optimal: refine iteration {it}/{refine_iters} "
                 f"(closed-loop re-solve on measured stage times)...")
            t_solve0 = time.time()
            allocator.refine_allocation(
                measured, max_time=solver_budget()
            )
            solve_s = time.time() - t_solve0
            gap = allocator.last_result.optimality_gap
            step, measured = measure_current_allocation(
                wm, f"optimal+refine{it}", ps, sanity=False
            )
            ran_refines = it
            refine_history.append(round(step, 4))
            if step < best_step:
                best_step, best_gap = step, gap
                best_snap = snapshot_allocation()
            record_best(step_times["even"], best_step, best_gap,
                        refine_history,
                        f"best of {it} refine iterations; final "
                        f"re-measurement not yet run")
        # Measured-time bottleneck polish (the reference's greedy-rebalance
        # analog, scaelum/dynamics/allocator.py:295-368, driven by REAL
        # stage times): the run-to-run headline lottery is which
        # allocation the (noisy profile -> calibration -> solve) chain
        # lands on — its realized max stage varies ~10% between runs.
        # Each move slides ONE unit off the realized bottleneck stage
        # through a chain of intermediate stages (their windows shift by
        # one; adjacent-only moves dead-end when both neighbors are slow
        # devices) to whichever stage the calibrated unit costs predict
        # can absorb it with a lower global max.  The re-measure reuses
        # every unchanged-or-recurring slice structure via the seed map,
        # so a move costs a fraction of a full pass.  Moves are
        # prediction-driven, not accepted-on-remeasure, so no
        # min-over-noisy-draws selection happens inside the loop; the
        # best-vs-initial choice below goes through the same fresh
        # final re-measurement as the refine path.
        polish_iters = int(os.getenv("SKYTPU_BENCH_POLISH", "6"))
        ran_polish = 0
        cost_sec = getattr(allocator, "_cost_override", None)
        if polish_iters > 0 and cost_sec is not None:
            cost_prefix = [0.0]
            for c in cost_sec:
                cost_prefix.append(cost_prefix[-1] + float(c))

            def cost_sum(a, b_):
                return cost_prefix[b_] - cost_prefix[a]

            # per-worker memory capacity exactly as the profiles fed the
            # solver (raw budget / stimulator skew) and the layer-memory
            # prefix over the profiled footprint: a chain candidate that
            # would overfill any changed stage is rejected, so the
            # polished allocation stays feasible under the instance's
            # memory regime (single-CPU emulation would not catch it)
            mem_prefix_p = [0.0]
            for m in layer_mem:
                mem_prefix_p.append(mem_prefix_p[-1] + float(m))

            def mem_sum(a, b_):
                return mem_prefix_p[b_] - mem_prefix_p[a]

            def worker_cap(w):
                raw = float(w.extra_config.get("mem_limit", mem_budget_mb))
                return raw / float(mem_skew[w.stim_index])

            cur_step, cur_measured = best_step, list(measured)
            visited = set()
            move_est = 0.15 * full_pass_s[0]  # refreshed from real moves
            for it in range(1, polish_iters + 1):
                # reserve only the even bracket behind a move: the final
                # re-measurement is OPTIONAL (the last-polish-step policy
                # below is the honest fallback), while polish is the one
                # mechanism that rescues a bad allocation draw — r05
                # trial 12 shed polish to protect a final pass it then
                # didn't need, and shipped the unpolished bad draw
                need = move_est + 0.55 * even_pass_s + 75
                if _time_left() < need:
                    note(f"polish stopped before move {it}: "
                         f"{_time_left():.0f}s left < {need:.0f}s needed")
                    _skip_phase("polish")
                    break
                workers = [
                    w for w in sorted(wm.worker_pool, key=lambda w: w.order)
                    if w.model_config
                ]
                S = len(workers)
                if S != len(cur_measured):
                    break
                svals = [float(w.extra_config["slowdown"]) for w in workers]
                taus = [t * sv for t, sv in zip(cur_measured, svals)]
                cur_max = max(taus)
                b = taus.index(cur_max)
                ranges, pos = [], 0
                for w in workers:
                    ranges.append((pos, pos + len(w.model_config)))
                    pos += len(w.model_config)

                def chain_candidate(k, direction):
                    """Slide ONE unit off stage b through k intermediate
                    stages to stage b+k*direction; returns (pred_max,
                    new_ranges) or None.  Middle stages keep their count
                    (window shifts by one); predictions use the
                    calibrated per-unit costs over the exact range
                    deltas, so arbitrary chain lengths cost O(1) each."""
                    lo, hi_ = ranges[b]
                    if hi_ - lo <= 1:
                        return None
                    end = b + k * direction
                    if not (0 <= end < S):
                        return None
                    new_ranges = list(ranges)
                    if direction < 0:
                        new_ranges[b] = (lo + 1, hi_)
                        for j in range(b - 1, end, -1):
                            a, e = ranges[j]
                            new_ranges[j] = (a + 1, e + 1)
                        a, e = ranges[end]
                        new_ranges[end] = (a, e + 1)
                    else:
                        new_ranges[b] = (lo, hi_ - 1)
                        for j in range(b + 1, end):
                            a, e = ranges[j]
                            new_ranges[j] = (a - 1, e - 1)
                        a, e = ranges[end]
                        new_ranges[end] = (a - 1, e)
                    pred = 0.0
                    for j in range(S):
                        if new_ranges[j] == ranges[j]:
                            t_j = taus[j]
                        else:
                            if (mem_sum(*new_ranges[j])
                                    > worker_cap(workers[j]) + 1e-9):
                                return None  # would overfill worker j
                            delta = (cost_sum(*new_ranges[j])
                                     - cost_sum(*ranges[j]))
                            t_j = (cur_measured[j] + delta) * svals[j]
                        pred = max(pred, t_j)
                    return pred, new_ranges

                visited.add(tuple(ranges))
                # best UNVISITED improving candidate: predictions that
                # disagree with measurement would otherwise ping-pong
                # between two allocations forever (each move looks
                # improving from the other side) — trial-8 r05 showed
                # exactly that cycle
                cands = []
                for direction in (-1, +1):
                    for k in range(1, S):
                        out = chain_candidate(k, direction)
                        if out and out[0] < cur_max * (1.0 - 1e-3):
                            cands.append(out)
                cands.sort(key=lambda o: o[0])
                best_pred, best_ranges = None, None
                for pred, nr in cands:
                    if tuple(nr) not in visited:
                        best_pred, best_ranges = pred, nr
                        break
                if best_ranges is None:
                    note(f"polish converged after {it - 1} moves "
                         f"(no unvisited predicted-improving chain)")
                    break
                for w, (a, e) in zip(workers, best_ranges):
                    w.model_config = model_cfg[a:e]
                ran_polish = it
                note(f"polish move {it}: predicted max "
                     f"{best_pred:.4f}s (was {cur_max:.4f}s)")
                cur_step, cur_measured = measure_current_allocation(
                    wm, f"optimal+polish{it}", ps, n_repeats=repeats + 2,
                    sanity=False, seed_times=opt_seed,
                )
                move_est = max(last_pass_s[0], 15.0)
                refine_history.append(round(cur_step, 4))
                if cur_step < best_step:
                    best_step = cur_step
                    best_snap = snapshot_allocation()
                record_best(step_times["even"], best_step, best_gap,
                            refine_history,
                            f"best after {it} polish moves; final "
                            f"re-measurement not yet run")

        # reserve the even drift-bracket's cost (the bigger variance
        # lever) before committing to the fresh final re-measurement —
        # on a slow-host day the final is the stage to shed, not the
        # bracket (trial 9: the final overran and the bracket died with
        # the alarm)
        bracket_reserve = (
            0.55 * even_pass_s + 30
            if os.getenv("SKYTPU_BENCH_EVEN_BRACKET", "1") != "0" else 0.0
        )
        if ((ran_refines > 0 or ran_polish > 0)
                and _time_left()
                > 0.55 * full_pass_s[0] + bracket_reserve + 45):
            # SELECT on the (noisy) loop scores, but REPORT a fresh
            # measurement of whichever allocation won — reporting the min
            # over N draws (even the initial's, conditional on it beating
            # the refined scores) would bias the headline upward (winner's
            # curse).
            restore_allocation(best_snap)
            final_step, _ = measure_current_allocation(
                wm, "optimal-selected", ps, n_repeats=repeats + 2,
                sanity=False,
            )
            refine_history.append(round(final_step, 4))
            step_times[alloc_type] = final_step
            final_remeasured = True
        elif ran_polish > 0 and ran_refines == 0:
            # no budget for the fresh pass: report the LAST polish
            # measurement — the loop's moves are prediction-driven (never
            # accepted on a measurement draw), so the last step is an
            # unconditional estimate, free of the min-over-noisy-draws
            # bias that reporting best-of would reintroduce
            note("final re-measurement skipped: insufficient budget; "
                 "reporting the last (prediction-driven) polish step")
            _skip_phase("final_remeasure")
            step_times[alloc_type] = cur_step
        else:
            if ran_refines > 0:
                note("final re-measurement skipped: insufficient budget; "
                     "reporting the best loop score")
                _skip_phase("final_remeasure")
                restore_allocation(best_snap)
            step_times[alloc_type] = best_step
        solver_gap = best_gap

    # Drift bracket (default on): the even baseline is measured BEFORE
    # the optimal pass, so monotone machine drift (thermal, background
    # load) lands entirely on one side of the subtraction — the r05
    # trials saw the even step wander 14.09 -> 15.16 s across runs.  A
    # second even measurement AFTER the optimal pass (cheap: every
    # stage program is cache-warm) brackets the optimal epoch; the
    # baseline is their mean, and both values ship in the artifact.
    even_steps = [round(step_times["even"], 4)]
    if os.getenv("SKYTPU_BENCH_EVEN_BRACKET", "1") != "0":
        if _time_left() > 0.5 * even_pass_s + 30:
            e2, _ = measure_current_allocation(
                even_wm, "even-recheck", ps, n_repeats=repeats + 2,
                sanity=False,
            )
            even_steps.append(round(e2, 4))
            step_times["even"] = (step_times["even"] + e2) / 2.0
        else:
            note("even drift bracket skipped: insufficient budget")
            _skip_phase("even_bracket")
    speedup_pct = (
        (step_times["even"] - step_times["optimal"]) / step_times["even"] * 100
    )

    # ADVICE r03: the headline runs at ffn/2 granularity while vs_baseline
    # divides by the reference's 55% measured at 1/3-encoder granularity.
    # Record the ffn/1 number too (schedule model on the real timed ffn/1
    # profile — same math evaluate_instance applies to the guard) so the
    # baseline comparison can be read at matching granularity.
    value_ffn1 = None
    if (os.getenv("SKYTPU_BENCH_EMIT_FFN1", "1") != "0" and ffn_shards != 1
            and _time_left() > profile_s * 1.3 + 45):
        from skycomputing_tpu.dynamics.headline import evaluate_instance

        note("ffn/1 reference-granularity number (schedule model on the "
             "timed ffn/1 profile)...")
        cfg_ffn1 = bert_layer_configs(
            cfg, num_encoder_units=layer_num, num_classes=3,
            deterministic=True, ffn_shards=1,
        )
        bench_ffn1 = ModelBenchmarker(
            cfg_ffn1,
            RandomTokenGenerator(batch_size=batch, seq_length=seq,
                                 vocab_size=cfg.vocab_size),
            timed=(profile_kind == "timed"),
        )
        c1, m1 = bench_ffn1.benchmark()
        out1 = evaluate_instance(
            c1, m1, slowdowns, num_microbatches=n_micro,
            mem_budget_mb=mem_budget_mb, sequential=sequential,
        )
        value_ffn1 = round(out1["speedup_pct"], 2)
        note(f"ffn/1 granularity: {value_ffn1}% "
             f"(gap {out1['solver_result'].optimality_gap:.4f})")
    elif ffn_shards != 1:
        note("ffn/1 side number skipped (budget or env)")
        if (os.getenv("SKYTPU_BENCH_EMIT_FFN1", "1") != "0"
                and _time_left() <= profile_s * 1.3 + 45):
            _skip_phase("ffn1")
    _RESULT.update(
        value=round(speedup_pct, 2),
        vs_baseline=round(speedup_pct / 55.0, 4),
        # non-finite gap (lower bound <= 0) must serialize as null,
        # not the invalid-JSON token Infinity
        solver_gap=(
            round(solver_gap, 4) if solver_gap is not None
            and np.isfinite(solver_gap) else None
        ),
        # measured emulated step times per closed-loop iteration
        # (optimal, then each refine_allocation re-solve)
        refine_steps=refine_history,
        even_steps=even_steps,
        polish_moves=ran_polish,
        final_remeasure=final_remeasured,
        calibration=calib_fit,
        # reference-granularity (ffn/1) speedup via the schedule
        # model on the timed ffn/1 profile — apples-to-apples with
        # the reference's 1/3-encoder allocation units
        value_ffn1_model=value_ffn1,
        compile_cache=_COMPILE_CACHE_DIR,
        partial=None,
    )
    _emit()
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BaseException as e:  # noqa: BLE001 - the JSON line must appear
        if not isinstance(e, SystemExit):
            import traceback

            traceback.print_exc()
            _RESULT["partial"] = (
                f"crashed: {type(e).__name__}: {e}"
            )
            _emit()
            sys.exit(1)
        raise
