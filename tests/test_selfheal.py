"""Chaos suite: deterministic fault injection + the self-healing loop.

Every scenario is scripted through ``dynamics/faults.py`` (seeded
``FaultPlan``), so "node 0 becomes 3x slower at iter N" replays
byte-for-byte.  The end-to-end test drives the full loop the ISSUE
demands: straggler injected -> EWMA detection -> measured-speed
re-allocation -> resume from the layer-indexed snapshot -> wall clock
beats the no-heal control run.
"""

import os
import os.path as osp
import time

import jax
import numpy as np
import optax
import pytest

from skycomputing_tpu.dataset import DataLoader, RandomBertDataset
from skycomputing_tpu.dynamics import (
    Allocator,
    FaultInjectionHook,
    FaultPlan,
    ParameterServer,
    WorkerManager,
)
from skycomputing_tpu.models import bert_config, bert_layer_configs
from skycomputing_tpu.ops import cross_entropy_loss
from skycomputing_tpu.parallel import PipelineModel
from skycomputing_tpu.runner import (
    CheckpointHook,
    HeartbeatHook,
    Hook,
    NanGuardHook,
    Runner,
    SelfHealHook,
)
from skycomputing_tpu.utils import backoff_delays, retry_call

pytestmark = pytest.mark.chaos

# one optimizer instance for the whole module: the stage-program cache is
# keyed by (layer configs, id(optimizer)), so sharing it lets the control
# and healed runs share compiled programs — the wall-clock comparison then
# measures scheduling, not duplicate compilation
_OPT = optax.sgd(1e-2)


class _StaticDeviceBench:
    """Homogeneous device profile; heterogeneity comes from the faults."""

    def __init__(self, wm):
        self._wm = wm

    def benchmark(self):
        return {
            f"worker{w.rank}": dict(time=1.0, avai_mem=1e6)
            for w in self._wm.worker_pool
        }


class _StaticModelBench:
    def __init__(self, n):
        self._n = n

    def benchmark(self):
        return [1.0] * self._n, [0.1] * self._n


class _BatchAdapter:
    """RandomBertDataset yields (ids, mask, segs); BERT wants (ids, segs, mask)."""

    def __init__(self, loader):
        self._loader = loader

    def __len__(self):
        return len(self._loader)

    def __iter__(self):
        for (ids, mask, segs), labels in self._loader:
            yield (ids, segs, mask), labels


class _IterClock(Hook):
    def __init__(self):
        self.times = []
        self._t = None

    def before_iter(self, r):
        self._t = time.perf_counter()

    def after_iter(self, r):
        self.times.append(time.perf_counter() - self._t)


def build_chaos_world(devices, n_workers=3, units=3, seed=0):
    """Even-allocated BERT world with a REAL allocator (static
    benchmarkers) — the substrate for the checkpoint/NaN/heartbeat
    scenarios, where model realism matters more than cost-model fit."""
    cfg = bert_config("tiny", dtype="float32", hidden_dropout_prob=0.0,
                      attention_probs_dropout_prob=0.0)
    mc = bert_layer_configs(cfg, num_encoder_units=units, num_classes=3,
                            deterministic=True)
    wm = WorkerManager()
    wm.load_worker_pool_from_config(
        [dict(name=f"n{i}", device_config=dict(device_index=i),
              extra_config={}) for i in range(n_workers)]
    )
    alloc = Allocator(mc, wm, _StaticModelBench(len(mc)),
                      _StaticDeviceBench(wm))
    alloc.even_allocate()
    ds = RandomBertDataset(num_samples=64, max_seq_length=16,
                           vocab_size=1024, seed=seed)
    loader = DataLoader(ds, batch_size=8, shuffle=False)
    (ids, mask, segs), _ = next(iter(loader))
    ps = ParameterServer(mc, example_inputs=(ids, segs, mask),
                         rng=jax.random.key(seed))
    model = PipelineModel(wm, ps, _OPT, cross_entropy_loss, devices=devices)
    return model, ps, wm, loader, alloc


# --------------------------------------------------------------------------
# utils/retry.py
# --------------------------------------------------------------------------

def test_retry_call_recovers_with_deterministic_backoff():
    calls = []
    sleeps = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("transient")
        return "ok"

    out = retry_call(flaky, attempts=4, base_delay_s=0.1, max_delay_s=1.0,
                     jitter=0.5, seed=7, sleep=sleeps.append)
    assert out == "ok" and len(calls) == 3
    # the sleep schedule is exactly the seeded one, every run
    assert sleeps == backoff_delays(4, 0.1, 1.0, 0.5, seed=7)[:2]
    assert all(0.1 <= s <= 0.9 for s in sleeps)


def test_retry_call_exhausts_and_reraises_original():
    def always():
        raise OSError("gone")

    sleeps = []
    with pytest.raises(OSError, match="gone"):
        retry_call(always, attempts=3, sleep=sleeps.append)
    assert len(sleeps) == 2  # attempts - 1 backoffs


def test_retry_call_does_not_retry_unlisted_exceptions():
    calls = []

    def corrupt():
        calls.append(1)
        raise ValueError("corrupt checkpoint")

    with pytest.raises(ValueError):
        retry_call(corrupt, attempts=5, sleep=lambda s: None)
    assert len(calls) == 1


def test_retry_call_deadline_clamps_and_expires():
    """The total-deadline budget: sleeps clamp to the remaining budget
    and a failure past the deadline re-raises the ORIGINAL exception
    immediately, attempts left or not (a rendezvous read or a fleet
    dispatch must give up within the caller's patience)."""
    fake_now = [0.0]
    sleeps = []

    def fake_sleep(s):
        sleeps.append(s)
        fake_now[0] += s

    calls = []

    def always():
        calls.append(1)
        raise OSError("gone")

    with pytest.raises(OSError, match="gone"):
        retry_call(always, attempts=10, base_delay_s=1.0, max_delay_s=8.0,
                   jitter=0.0, seed=0, sleep=fake_sleep,
                   deadline_s=4.5, clock=lambda: fake_now[0])
    # schedule without a deadline would be 1, 2, 4, 8, ... — the budget
    # admits 1 + 2 then clamps the third sleep to the remaining 1.5s,
    # and the next failure (past the deadline) re-raises: 4 calls total
    assert sleeps == [1.0, 2.0, 1.5]
    assert len(calls) == 4
    # un-deadlined behavior is untouched
    assert backoff_delays(4, 1.0, 8.0, 0.0, seed=0) == [1.0, 2.0, 4.0]


def test_retry_call_deadline_zero_means_single_round():
    """deadline_s=0: the first attempt runs, the first retryable
    failure propagates — no sleeps at all."""
    calls = []

    def always():
        calls.append(1)
        raise OSError("gone")

    sleeps = []
    with pytest.raises(OSError):
        retry_call(always, attempts=5, sleep=sleeps.append,
                   deadline_s=0.0)
    assert len(calls) == 1 and sleeps == []
    with pytest.raises(ValueError, match="deadline_s"):
        retry_call(lambda: 1, deadline_s=-1.0)


# --------------------------------------------------------------------------
# FaultPlan
# --------------------------------------------------------------------------

def test_fault_plan_validates_and_replays_deterministically():
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultPlan([dict(iter=0, kind="meteor")])
    with pytest.raises(ValueError, match="missing 'iter'"):
        FaultPlan([dict(kind="stall", seconds=1.0)])
    # per-kind required fields fail at CONSTRUCTION, not mid-chaos-run
    with pytest.raises(ValueError, match="missing required"):
        FaultPlan([dict(iter=50, kind="stall")])
    with pytest.raises(ValueError, match="missing required"):
        FaultPlan([dict(iter=1, kind="slowdown", worker=0)])
    with pytest.raises(ValueError, match="missing required"):
        FaultPlan([dict(iter=1, kind="corrupt_checkpoint")])

    a = FaultPlan([dict(iter=3, kind="stall", seconds=0.1)], seed=5)
    b = FaultPlan([dict(iter=3, kind="stall", seconds=0.1)], seed=5)
    assert [a.draw_fraction() for _ in range(4)] == [
        b.draw_fraction() for _ in range(4)
    ]
    stim_plan = FaultPlan.from_stimulator(4, at_iter=2)
    assert len(stim_plan.events) == 4
    assert all(e["kind"] == "slowdown" and e["iter"] == 2
               for e in stim_plan.events)
    # same seeded draw as the Stimulator itself
    from skycomputing_tpu.stimulator import Stimulator

    stim = Stimulator(4)
    assert stim_plan.events[1]["factor"] == stim.compute_slowdown(1)


# --------------------------------------------------------------------------
# the tentpole: straggler -> detect -> re-allocate -> recover
# --------------------------------------------------------------------------

_MM_LAYERS = 10
_MM_FEATURES = 384


class _ArrayLoader:
    """Seeded synthetic (x, labels) batches for the matmul pipeline."""

    def __init__(self, features, batch=32, n=8, seed=0):
        rng = np.random.default_rng(seed)
        self._batches = [
            (
                rng.normal(size=(batch, features)).astype(np.float32),
                rng.integers(0, features, size=(batch,)).astype(np.int32),
            )
            for _ in range(n)
        ]

    def __len__(self):
        return len(self._batches)

    def __iter__(self):
        return iter(self._batches)


def build_matmul_world(devices, n_workers=3, seed=0):
    """A UNIFORM pipeline (identical MatmulStack layers): the flat static
    cost profile is exact, stage programs depend only on slice LENGTH,
    and compute scales with ``features`` — the cleanest substrate for
    deterministic straggler scenarios.  All workers share device 0 so a
    repartition never recompiles (jit caches per (config, device)): the
    wall-clock comparison isolates scheduling from one-time XLA work,
    which a long-running production job amortizes anyway."""
    mc = [
        dict(layer_type="MatmulStack", features=_MM_FEATURES, depth=3,
             dtype="float32")
    ] * _MM_LAYERS
    wm = WorkerManager()
    wm.load_worker_pool_from_config(
        [dict(name=f"n{i}", device_config=dict(device_index=0),
              extra_config={}) for i in range(n_workers)]
    )
    alloc = Allocator(mc, wm, _StaticModelBench(len(mc)),
                      _StaticDeviceBench(wm))
    alloc.even_allocate()
    loader = _ArrayLoader(_MM_FEATURES, seed=seed)
    x, _ = next(iter(loader))
    ps = ParameterServer(mc, example_inputs=(x,), rng=jax.random.key(seed))
    model = PipelineModel(wm, ps, _OPT, cross_entropy_loss, devices=devices)
    return model, ps, wm, loader, alloc


def _prewarm_slice_programs(mc, ps, x, max_len):
    """Compile fwd/bwd/update for every slice length the solver might
    emit, OUTSIDE any timed window.  Uniform layers mean a slice's
    programs depend only on its length, so this is cheap and exhaustive —
    the wall-clock comparison then measures scheduling, not one-time XLA
    compilation (which a long-running production job amortizes anyway)."""
    import jax.numpy as jnp

    from skycomputing_tpu.parallel.pipeline import get_stage_programs

    for n in range(1, max_len + 1):
        programs = get_stage_programs(mc[:n], _OPT)
        params = [jax.tree_util.tree_map(np.array, p)
                  for p in ps.get_layer_slice(0, n)]
        out = programs.fwd(params, (x,), None)
        dy = jax.tree_util.tree_map(jnp.zeros_like, out)
        grads, _ = programs.bwd(params, (x,), None, dy)
        opt_state = _OPT.init(params)
        jax.block_until_ready(programs.update(params, opt_state, grads))


@pytest.mark.slow
def test_straggler_triggers_one_heal_and_beats_no_heal_control(devices,
                                                               tmp_path):
    """Seeded FaultPlan makes worker 0 (initially the largest stage) 3x
    slower mid-run; the SelfHealHook must detect it, re-allocate via the
    measured device speeds, resume from the layer-indexed snapshot, and
    the healed run's wall clock must beat the no-heal control driven by
    the SAME plan."""
    N_ITERS = 48
    # iter 5: after grace (1 iter) + the two 2-iter baseline windows
    FAULT = dict(iter=5, kind="slowdown", worker=0, factor=3.0)

    # one throwaway world warms every slice-length program a 3-worker
    # re-solve can plausibly emit (a fast device never takes > 6 of the 10
    # uniform layers — that bottleneck would always lose)
    model_w, ps_w, _, loader_w, _ = build_matmul_world(devices, seed=9)
    x_w, _ = next(iter(loader_w))
    _prewarm_slice_programs(list(ps_w._model_config), ps_w, x_w, max_len=6)
    model_w.train_step(*next(iter(loader_w)), rng=jax.random.key(0))

    # -- control: same fault, no healing -----------------------------------
    model_c, ps_c, wm_c, loader_c, _ = build_matmul_world(devices, seed=1)
    runner_c = Runner(model_c, ps_c, wm_c, max_epochs=100, max_iters=N_ITERS)
    clock_c = _IterClock()
    runner_c.register_hook(FaultInjectionHook(FaultPlan([FAULT])))
    runner_c.register_hook(clock_c)
    runner_c.train(loader_c)

    # -- healed run --------------------------------------------------------
    model_h, ps_h, wm_h, loader_h, alloc_h = build_matmul_world(devices,
                                                                seed=1)
    snapshot = str(tmp_path / "selfheal_snapshot.msgpack")
    heal = SelfHealHook(
        alloc_h, window=2, k_windows=2, threshold=1.35, grace_iters=1,
        max_heals=1, measure_repeats=1, measure_inner=1, solver_time_s=5.0,
        snapshot_path=snapshot,
    )
    runner_h = Runner(model_h, ps_h, wm_h, max_epochs=100, max_iters=N_ITERS)
    clock_h = _IterClock()
    runner_h.register_hook(FaultInjectionHook(FaultPlan([FAULT])))
    # clock AFTER the heal hook: after_iter hooks run in registration
    # order, so the heal's full cost (measure + re-solve + repartition)
    # lands INSIDE a clocked window and counts against the healed run
    runner_h.register_hook(heal)
    runner_h.register_hook(clock_h)
    runner_h.train(loader_h)

    # exactly one re-allocation, straggler-attributed
    heals = [e for e in heal.events if e["kind"] == "heal"]
    assert len(heals) == 1, heal.events
    assert heal.heals == 1
    ev = heals[0]
    assert max(ev["divergence"], key=ev["divergence"].get) == 0
    assert ev["divergence"][0] > 1.5  # straggler clearly dominant

    # the slow node sheds layers (it held 4 of 10 — the even split's
    # largest stage); coverage stays contiguous and complete
    slow = next(w for w in wm_h.worker_pool if w.stim_index == 0)
    assert len(slow.model_config or []) < 4, ev
    total = []
    for w in sorted(wm_h.worker_pool, key=lambda w: w.rank):
        total.extend(w.model_config or [])
    assert total == alloc_h._model_cfg

    # snapshot was written before repartition and restores cleanly
    assert osp.exists(snapshot)
    ps_check = ParameterServer(alloc_h._model_cfg, init=False)
    ps_check.load_weights_from_file(snapshot)
    assert len(ps_check.params) == len(alloc_h._model_cfg)

    # training kept running after the heal, to the full iteration budget
    assert runner_h.iter == N_ITERS

    # post-heal steady state is faster than the straggler era (skip 2
    # iters after the heal for residual warmup)
    heal_at = ev["iter"]
    straggler_era = clock_h.times[FAULT["iter"] + 1 : heal_at - 1]
    post = clock_h.times[heal_at + 2 :]
    assert len(straggler_era) >= 2 and len(post) >= 5
    assert (sum(post) / len(post)) < (
        sum(straggler_era) / len(straggler_era)
    ), (straggler_era, post)

    # headline: self-healing beats riding out the straggler.  Training
    # wall clock = the sum of per-iteration windows; the healed run's
    # windows include the full heal cost (clock registered after the heal
    # hook), the control's include the straggler for the whole run.
    t_control = sum(clock_c.times)
    t_healed = sum(clock_h.times)
    assert t_healed < t_control, (t_healed, t_control)


class _TransientStallClock:
    """Deterministic iteration clock emulating ONE stalled iteration:
    every iteration reads as ``tick_s`` except ``stall_iter``, which
    reads ``tick_s + stall_s``.  The hook reads the clock exactly twice
    per iteration (before_iter / after_iter, in order), so the end-read
    advances by that iteration's cost.  Same rationale as
    ``_EmulatedIterClock``: with ~30 ms real steps, host contention in
    a loaded full-suite run inflated post-stall iterations past the
    1.5x threshold and the real-clock EWMA healed on machine noise —
    the k-window debounce under test never got a clean signal."""

    def __init__(self, stall_iter: int, stall_s: float,
                 tick_s: float = 0.05):
        self._now = 0.0
        self._reads = 0
        self._stall_iter = stall_iter
        self._stall_s = stall_s
        self._tick_s = tick_s

    def __call__(self) -> float:
        it, end_read = divmod(self._reads, 2)
        if end_read:
            self._now += self._tick_s + (
                self._stall_s if it == self._stall_iter else 0.0
            )
        self._reads += 1
        return self._now


def test_transient_stall_does_not_trigger_heal(devices):
    """A one-iteration wedge (fault kind 'stall') must not cause a
    re-allocation: the divergence is not sustained."""
    model, ps, wm, loader, alloc = build_matmul_world(devices, seed=2)
    # iter 9: inside a DETECTION window (baseline learned over iters 2-7)
    plan = FaultPlan([dict(iter=9, kind="stall", seconds=0.4)])
    heal = SelfHealHook(alloc, window=3, k_windows=2, threshold=1.5,
                        grace_iters=2, max_heals=1,
                        clock=_TransientStallClock(stall_iter=9,
                                                   stall_s=0.4))
    runner = Runner(model, ps, wm, max_epochs=100, max_iters=18)
    runner.register_hook(FaultInjectionHook(plan))
    runner.register_hook(heal)
    runner.train(loader)
    assert heal.heals == 0
    assert not [e for e in heal.events if e["kind"] == "heal"]


def test_nan_fault_trips_nan_guard_and_checkpoint_skip(devices, tmp_path):
    """NaN injection (bad DIMM) -> NanGuardHook raises -> the aborted run
    must NOT persist the poisoned params as the newest checkpoint."""
    model, ps, wm, loader, _ = build_chaos_world(devices, seed=3)
    save_dir = str(tmp_path / "nan_ck")
    runner = Runner(model, ps, wm, max_epochs=100, max_iters=12)
    runner.register_hook(FaultInjectionHook(
        FaultPlan([dict(iter=3, kind="nan", worker=1)])
    ))
    runner.register_hook(NanGuardHook(action="raise"))
    runner.register_hook(CheckpointHook(save_path=save_dir, save_interval=1))
    with pytest.raises(FloatingPointError, match="non-finite"):
        runner.train(_BatchAdapter(loader))
    assert runner.aborted is True
    assert not os.path.exists(save_dir) or os.listdir(save_dir) == []


def test_drop_beat_fault_suppresses_heartbeat(devices):
    """Dropped beats (process missing its beat window) skip exactly the
    scheduled collectives — and only those."""
    model, ps, wm, loader, _ = build_chaos_world(devices, seed=4)
    plan = FaultPlan([
        dict(iter=2, kind="drop_beat"),
        dict(iter=4, kind="drop_beat"),
    ])
    runner = Runner(model, ps, wm, max_epochs=100, max_iters=6)
    hb = HeartbeatHook(interval=1, timeout_s=60.0, action="stop")
    fh = FaultInjectionHook(plan)
    runner.register_hook(fh)
    runner.register_hook(hb)
    runner.train(_BatchAdapter(loader))
    assert runner.iter == 6
    # 6 iters, beat every iter, 2 dropped
    assert hb.heartbeat.beats == 4
    assert hb.heartbeat.failed is False
    # both armed drops were actually consumed by a scheduled beat
    drops = [e for e in fh.applied if e["kind"] == "drop_beat"]
    assert len(drops) == 2
    assert all(e.get("consumed", True) for e in drops)

    # interval mismatch: a drop armed where no beat is scheduled must be
    # recorded as NOT consumed, not silently counted as a suppression
    model2, ps2, wm2, loader2, _ = build_chaos_world(devices, seed=4)
    plan2 = FaultPlan([dict(iter=2, kind="drop_beat")])
    runner2 = Runner(model2, ps2, wm2, max_epochs=100, max_iters=6)
    hb2 = HeartbeatHook(interval=5, timeout_s=60.0, action="stop")
    fh2 = FaultInjectionHook(plan2)
    runner2.register_hook(fh2)
    runner2.register_hook(hb2)
    runner2.train(_BatchAdapter(loader2))
    drop2 = [e for e in fh2.applied if e["kind"] == "drop_beat"]
    assert drop2 and drop2[0]["consumed"] is False
    assert hb2.heartbeat.beats == 1  # iter 5's beat happened normally


def test_corrupt_checkpoint_fault_detected_on_load(devices, tmp_path):
    """A checkpoint truncated by the fault plan (torn write) must fail the
    load with a clear error naming the file — not a deep flax traceback."""
    model, ps, wm, loader, _ = build_chaos_world(devices, seed=5)
    save_dir = str(tmp_path / "torn")
    runner = Runner(model, ps, wm, max_epochs=1, max_iters=100)
    runner.register_hook(CheckpointHook(save_path=save_dir, save_interval=1))
    runner.train(list(_BatchAdapter(loader))[:2])
    ckpt = osp.join(save_dir, "epoch_1.msgpack")
    assert osp.exists(ckpt)

    plan = FaultPlan([], seed=11)
    target = plan.corrupt_checkpoint(save_dir, keep_fraction=0.5)
    assert target == ckpt

    ps2 = ParameterServer(list(ps._model_config), init=False)
    with pytest.raises(ValueError, match="corrupt or truncated"):
        ps2.load_weights_from_file(ckpt)
    # and the same clear error through the hook's restore path
    runner2 = Runner(model, ps, wm, max_epochs=0, max_iters=0)
    runner2.register_hook(CheckpointHook(load_checkpoint_from=ckpt))
    with pytest.raises(ValueError, match="corrupt or truncated"):
        runner2.train(_BatchAdapter(loader))


def test_atomic_save_survives_kill_during_write(devices, tmp_path,
                                                monkeypatch):
    """kill -9 during a save == dying before the atomic publish: the
    previous checkpoint must remain the newest complete file."""
    model, ps, wm, loader, _ = build_chaos_world(devices, seed=6)
    ckpt = str(tmp_path / "weights.msgpack")
    ps.save_weights_to_file(ckpt)
    good = open(ckpt, "rb").read()

    import skycomputing_tpu.dynamics.parameter_server as ps_mod

    def killed(src, dst):
        raise OSError("simulated kill -9 before publish")

    monkeypatch.setattr(ps_mod.os, "replace", killed)
    with pytest.raises(OSError, match="simulated kill"):
        ps.save_weights_to_file(ckpt)
    monkeypatch.undo()

    # the published checkpoint is byte-identical to the last good save and
    # still loads; the torn bytes only ever lived in the .tmp sidecar
    assert open(ckpt, "rb").read() == good
    ps2 = ParameterServer(list(ps._model_config), init=False)
    ps2.load_weights_from_file(ckpt)
    assert len(ps2.params) == len(ps._model_config)


class _EmulatedIterClock:
    """Deterministic iteration clock for SelfHealHook: every read
    advances by a tick proportional to the pipeline's WORST emulated
    slowdown, so detection follows the injected fault exactly instead of
    racing real wall time — under full-suite load the real-clock EWMA
    read every iteration as slow (or the baseline as degraded) and this
    test flaked (CHANGES.md PR 11/12)."""

    def __init__(self, model, tick_s: float = 0.05):
        self._model = model
        self._tick_s = tick_s
        self._now = 0.0

    def __call__(self) -> float:
        worst = max(s.slowdown for s in self._model.stages)
        self._now += self._tick_s * worst
        return self._now


def _emulated_stage_times(model, layer_s: float = 0.01):
    """``measure_stage_times`` from the injected speeds alone: a stage
    takes ``layer_s`` a layer times its emulated slowdown, which is what
    the real pass reads on an idle machine (uniform layers, one device).
    On a loaded one its single wall-clock sample per stage shape wanders
    by a third, and the straggler's scale, a ratio of two such samples,
    read 1.487 against the 1.5 asserted below (ROADMAP D8).  The
    divergence, calibration and payload code under test is the real
    one; ``tests/test_pipeline.py`` covers the real measurement and its
    slowdown factor."""

    def measure(data, **_):
        return [layer_s * s.num_layers * s.slowdown for s in model.stages]

    return measure


def test_selfheal_exit_mode_stages_payload_and_exits(devices, tmp_path):
    """Supervised path: instead of repartitioning in process, the hook
    snapshots, stages the measured device scales for the rendezvous, and
    exits with REALLOC_RC for the ElasticSupervisor to re-form."""
    import json

    from skycomputing_tpu.parallel.elastic import REALLOC_RC

    model, ps, wm, loader, alloc = build_matmul_world(devices, seed=7)
    model.measure_stage_times = _emulated_stage_times(model)
    rdv = tmp_path / "rdv"
    rdv.mkdir()
    snapshot = str(tmp_path / "exit_snapshot.msgpack")
    # exit mode abandons the in-memory parameter server with the process:
    # a persisted snapshot is mandatory
    with pytest.raises(ValueError, match="snapshot_path"):
        SelfHealHook(alloc, mode="exit")
    heal = SelfHealHook(
        alloc, window=2, k_windows=2, threshold=1.35, grace_iters=1,
        measure_repeats=1, measure_inner=1, mode="exit",
        snapshot_path=snapshot, rendezvous_dir=str(rdv),
        clock=_EmulatedIterClock(model),
    )
    runner = Runner(model, ps, wm, max_epochs=100, max_iters=40)
    runner.register_hook(FaultInjectionHook(
        FaultPlan([dict(iter=5, kind="slowdown", worker=0, factor=3.0)])
    ))
    runner.register_hook(heal)
    with pytest.raises(SystemExit) as exc_info:
        runner.train(loader)
    assert exc_info.value.code == REALLOC_RC
    assert runner.aborted is False  # a planned exit, not a crash

    assert osp.exists(snapshot)
    payload = json.loads((rdv / "realloc.json").read_text())
    assert payload["device_scale"]["0"] > 1.5  # straggler dominant
    assert len(payload["measured_stage_times"]) == 3

    # a fresh allocator (fresh process emulation) applies the carried
    # scales and routes work away from the degraded node: with uniform
    # layers it must shed layers from the even split's 4
    model2, ps2, wm2, _, alloc2 = build_matmul_world(devices, seed=7)
    alloc2.apply_device_scales(payload["device_scale"])
    alloc2.optimal_allocate(max_time=5.0)
    slow = next(w for w in wm2.worker_pool if w.stim_index == 0)
    assert len(slow.model_config or []) < 4
