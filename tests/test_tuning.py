"""Closed-loop autotuning contracts (``skycomputing_tpu/tuning/``).

Three layers, cheapest first: the advisor's signature table on
synthetic traces (pure dict-in/dict-out), the verify-then-apply /
rollback state machine on a live Runner with a scripted advisor
(deterministic — no timing races), and the E2E acceptance scenario: a
fault-injected straggler world where the tuner converges with no human
in the loop to a plan ``trace_report --baseline`` certifies as faster.
"""

import json
import os.path as osp

import jax
import numpy as np
import optax
import pytest

from skycomputing_tpu import telemetry
from skycomputing_tpu.analysis.plan_check import verify_tuning_knobs
from skycomputing_tpu.dynamics import (
    Allocator,
    ParameterServer,
    WorkerManager,
)
from skycomputing_tpu.models import bert_config, bert_layer_configs
from skycomputing_tpu.ops import cross_entropy_loss
from skycomputing_tpu.parallel import PipelineModel
from skycomputing_tpu.runner import AutotuneHook, Runner
from skycomputing_tpu.telemetry.analysis import (
    analyze,
    load_events,
    measured_stage_seconds,
    serving_padding_fraction,
)
from skycomputing_tpu.tuning import Proposal, TuningAdvisor
from skycomputing_tpu.tuning.advisor import (
    MICROBATCH_COUNT,
    PIPELINE_SCHEDULE,
    QUEUE_PRESSURE,
    SKEWED_BUCKETS,
    STRAGGLER,
)
from tools.bench_autotune import run_smoke
from tools.trace_report import main as report_main

pytestmark = pytest.mark.tune

STRAGGLER_FIXTURE = osp.join(
    osp.dirname(osp.dirname(osp.abspath(__file__))),
    "tools", "fixtures", "trace_straggler.json",
)

_OPT = optax.sgd(1e-2)


@pytest.fixture(autouse=True)
def _no_leaked_tracer():
    telemetry.disable_tracing()
    yield
    telemetry.disable_tracing()


# --------------------------------------------------------------------------
# advisor signatures on synthetic traces
# --------------------------------------------------------------------------


def test_straggler_signature_proposes_device_refinement():
    report = analyze(load_events(STRAGGLER_FIXTURE))
    # the analysis additions the tuner consumes
    assert set(report["stage_busy_ms"]) == {"0", "1", "2"}
    measured = measured_stage_seconds(report)
    assert len(measured) == 3
    assert measured.index(max(measured)) == 1

    proposal = TuningAdvisor().propose_training(
        report, schedule="gpipe", num_microbatches=2, batch_size=8
    )
    assert proposal is not None
    assert proposal.knob == "allocation"
    assert proposal.signature == STRAGGLER
    assert list(proposal.value) == pytest.approx(measured)
    # blocking the signature silences it (the convergence mechanism):
    # the fixture's bubble is high, so the advisor falls through to the
    # next signature in priority order, and blocking everything is clean
    fallthrough = TuningAdvisor().propose_training(
        report, schedule="gpipe", num_microbatches=2,
        blocked={STRAGGLER},
    )
    assert fallthrough is not None
    assert fallthrough.signature == PIPELINE_SCHEDULE
    blocked_all = TuningAdvisor().propose_training(
        report, schedule="gpipe", num_microbatches=2,
        blocked={STRAGGLER, PIPELINE_SCHEDULE, MICROBATCH_COUNT},
    )
    assert blocked_all is None


def test_bubble_signature_walks_schedule_then_microbatches():
    report = {
        "stage_busy_ms": {"0": 30.0, "1": 32.0},
        "bubble_fraction": 0.55,
        "steps": {"count": 8, "p50_ms": 12.0},
    }
    advisor = TuningAdvisor()
    p1 = advisor.propose_training(
        report, schedule="gpipe", num_microbatches=4, batch_size=8
    )
    assert (p1.knob, p1.value, p1.signature) == (
        "schedule", "1f1b", PIPELINE_SCHEDULE
    )
    # already on 1f1b -> deepen the fill instead
    p2 = advisor.propose_training(
        report, schedule="1f1b", num_microbatches=4, batch_size=8
    )
    assert (p2.knob, p2.value, p2.signature) == (
        "microbatches", 8, MICROBATCH_COUNT
    )
    # indivisible batch suppresses the microbatch move
    assert advisor.propose_training(
        report, schedule="1f1b", num_microbatches=4, batch_size=12
    ) is None


def test_clean_trace_is_a_no_op():
    report = {
        "stage_busy_ms": {"0": 90.0, "1": 92.0, "2": 91.0},
        "bubble_fraction": 0.08,
        "steps": {"count": 10, "p50_ms": 10.0},
    }
    assert TuningAdvisor().propose_training(
        report, schedule="1f1b", num_microbatches=4, batch_size=8
    ) is None


def test_serving_signatures():
    advisor = TuningAdvisor()
    skew = {
        "stage_busy_ms": {"0": 50.0},
        "bubble_fraction": 0.2,
        "serving": {
            "prefill_waves": 20, "decode_ticks": 80, "queue_stalls": 0,
            "padding_fraction": 1 - 200 / (64 * 20),
            "buckets": {"64": {"waves": 20, "requests": 20,
                               "tokens": 200, "padded_fraction": 0.84}},
        },
    }
    p = advisor.propose_serving(skew, buckets=(64,), num_slots=4,
                                max_len=128)
    assert p.knob == "buckets" and p.signature == SKEWED_BUCKETS
    assert 64 in p.value and min(p.value) < 64
    assert serving_padding_fraction(skew["serving"]) == pytest.approx(
        1 - 200 / (64 * 20)
    )

    stalls = {
        "stage_busy_ms": {"0": 50.0},
        "bubble_fraction": 0.2,
        "serving": {
            "prefill_waves": 10, "decode_ticks": 30, "queue_stalls": 25,
            "buckets": {"16": {"waves": 10, "requests": 10,
                               "tokens": 150, "padded_fraction": 0.06}},
        },
    }
    p = advisor.propose_serving(stalls, buckets=(16,), num_slots=2,
                                max_len=64)
    assert (p.knob, p.value, p.signature) == ("slots", 4, QUEUE_PRESSURE)

    healthy = {
        "stage_busy_ms": {"0": 50.0},
        "bubble_fraction": 0.2,
        "serving": {
            "prefill_waves": 10, "decode_ticks": 30, "queue_stalls": 0,
            "buckets": {"16": {"waves": 10, "requests": 10,
                               "tokens": 150, "padded_fraction": 0.06}},
        },
    }
    assert advisor.propose_serving(
        healthy, buckets=(16,), num_slots=2, max_len=64
    ) is None


def test_decode_tail_signature_enables_then_shrinks_chunking():
    """tpot p95/p50 past the threshold proposes the prefill_chunk
    knob: enable at the largest sub-max bucket when off, shrink one
    bucket when on, nothing left at the floor; blocked and
    missing-percentile reports stay quiet."""
    from skycomputing_tpu.tuning.advisor import DECODE_TAIL

    advisor = TuningAdvisor(tail_ratio_threshold=3.0)
    tail = {
        "stage_busy_ms": {"0": 50.0},
        "bubble_fraction": 0.2,
        "serving": {
            "prefill_waves": 10, "decode_ticks": 40, "queue_stalls": 0,
            "tpot_p50_s": 0.03, "tpot_p95_s": 0.60,  # 20x blowup
            "buckets": {"16": {"waves": 10, "requests": 10,
                               "tokens": 150}},
        },
    }
    p = advisor.propose_serving(tail, buckets=(16, 32, 64), num_slots=4,
                                max_len=128, prefill_chunk=None)
    assert (p.knob, p.value, p.signature) == (
        "prefill_chunk", 32, DECODE_TAIL
    )
    assert p.metric == "tpot_tail_ratio"
    # already chunking -> shrink one bucket
    p = advisor.propose_serving(tail, buckets=(16, 32, 64), num_slots=4,
                                max_len=128, prefill_chunk=32)
    assert (p.knob, p.value) == ("prefill_chunk", 16)
    # at the floor -> nothing left to actuate
    assert advisor.propose_serving(
        tail, buckets=(16, 32, 64), num_slots=4, max_len=128,
        prefill_chunk=16,
    ) is None
    # blocked signature falls through (no other signature fires here)
    assert advisor.propose_serving(
        tail, buckets=(16, 32, 64), num_slots=4, max_len=128,
        prefill_chunk=None, blocked={DECODE_TAIL},
    ) is None
    # a trace-only report (no merged SLO percentiles) never fires
    quiet = dict(tail, serving={
        k: v for k, v in tail["serving"].items()
        if not k.startswith("tpot_")
    })
    assert advisor.propose_serving(
        quiet, buckets=(16, 32, 64), num_slots=4, max_len=128,
        prefill_chunk=None,
    ) is None
    # a healthy tail stays quiet
    calm = dict(tail, serving=dict(tail["serving"], tpot_p95_s=0.05))
    assert advisor.propose_serving(
        calm, buckets=(16, 32, 64), num_slots=4, max_len=128,
        prefill_chunk=None,
    ) is None


def test_serving_autotuner_actuates_prefill_chunk():
    """The acting layer routes a decode-tail proposal through
    reconfigure: the engine ends up chunking, the revert snapshot can
    undo it, and the window-SLO merge feeds the advisor the ratio it
    thresholds."""
    from skycomputing_tpu.builder import build_layer_stack
    from skycomputing_tpu.models.gpt import GptConfig, gpt_layer_configs
    from skycomputing_tpu.serving import ServingEngine
    from skycomputing_tpu.tuning.autotune import ServingAutotuner

    cfg = GptConfig(vocab_size=256, hidden_size=32, num_hidden_layers=2,
                    num_attention_heads=2, max_position_embeddings=64,
                    dropout_prob=0.0, dtype="float32")
    layer_cfgs = gpt_layer_configs(cfg, deterministic=True)
    stack = build_layer_stack(layer_cfgs)
    params = stack.init(jax.random.key(0), np.ones((1, 5), np.int32))
    engine = ServingEngine(layer_cfgs, list(params), num_slots=2,
                           max_len=48, buckets=(8, 16),
                           kv_layout="paged", page_size=8)
    tuner = ServingAutotuner(engine)
    # the windowed SLO merge: enough samples -> percentiles land in
    # the serving section; the tail metric reads them back
    engine.stats.tpot_s.extend([0.03, 0.031, 0.029, 0.030, 0.9])
    report = {"serving": {"prefill_waves": 1, "decode_ticks": 4,
                          "queue_stalls": 0, "buckets": {}}}
    tuner._merge_window_slo(report, engine)
    assert report["serving"]["tpot_p50_s"] == pytest.approx(0.030)
    assert report["serving"]["tpot_p95_s"] == pytest.approx(0.9)
    assert tuner._metric(report, "tpot_tail_ratio") == pytest.approx(
        0.9 / 0.030
    )
    # actuation: the knob reaches reconfigure and the engine chunks
    engine.reconfigure(prefill_chunk=8)
    assert engine.prefill_chunk == 8
    engine.reconfigure(prefill_chunk=0)
    assert engine.prefill_chunk is None


def test_bench_autotune_smoke():
    """The CI lint job's exact decide-step invocation."""
    assert run_smoke() == 0


def test_verify_tuning_knobs_contract():
    assert verify_tuning_knobs(schedule="1f1b", num_microbatches=4,
                               batch_size=8).ok
    assert not verify_tuning_knobs(schedule="steady").ok
    assert not verify_tuning_knobs(num_microbatches=3, batch_size=8).ok
    assert not verify_tuning_knobs(num_microbatches=0).ok
    assert verify_tuning_knobs(buckets=(8, 16), max_len=32,
                               num_slots=4).ok
    assert not verify_tuning_knobs(buckets=(8, 64), max_len=32).ok
    assert not verify_tuning_knobs(buckets=(), max_len=32).ok
    assert not verify_tuning_knobs(num_slots=-1).ok
    # malformed bucket entries degrade to PlanIssues, never TypeError
    # out of the verifier (the PR 4 hardening contract)
    assert not verify_tuning_knobs(buckets=[None, 64]).ok
    assert not verify_tuning_knobs(buckets=["a", 2.5]).ok
    # chunked-prefill / speculation knob schema
    assert verify_tuning_knobs(buckets=(8, 16), max_len=32,
                               prefill_chunk=8, spec_k=2).ok
    assert verify_tuning_knobs(spec_k=0).ok  # 0 = disabled
    assert not verify_tuning_knobs(buckets=(8, 16), max_len=32,
                                   prefill_chunk=12).ok  # off-bucket
    assert not verify_tuning_knobs(prefill_chunk=0).ok
    assert not verify_tuning_knobs(spec_k=-1).ok
    assert not verify_tuning_knobs(spec_k=True).ok
    assert not verify_tuning_knobs(max_len=4, spec_k=6).ok
    with pytest.raises(Exception):
        verify_tuning_knobs(schedule="bogus").raise_if_failed()


def test_trace_report_json_carries_baseline_gate(tmp_path, capsys):
    baseline = tmp_path / "base.json"
    baseline.write_text(json.dumps({"step_ms": 100.0}))
    rc = report_main([STRAGGLER_FIXTURE, "--json",
                      "--baseline", str(baseline)])
    out = capsys.readouterr().out
    report = json.loads(out.strip().splitlines()[-1])
    assert rc == 0
    assert report["baseline_gate"]["ok"] is True
    assert report["stage_busy_ms"]["1"] > report["stage_busy_ms"]["0"]
    # a regressing baseline flips the verdict and the exit code
    tight = tmp_path / "tight.json"
    tight.write_text(json.dumps({"step_ms": 1.0}))
    rc = report_main([STRAGGLER_FIXTURE, "--json",
                      "--baseline", str(tight)])
    report = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 2
    assert report["baseline_gate"]["ok"] is False


# --------------------------------------------------------------------------
# hook state machine (scripted advisor — deterministic)
# --------------------------------------------------------------------------


class _ScriptedAdvisor:
    """Returns the queued proposals once each, then None forever."""

    def __init__(self, *proposals):
        self._proposals = list(proposals)

    def propose_training(self, report, *, blocked=(), **knobs):
        while self._proposals:
            p = self._proposals.pop(0)
            if p.signature not in blocked:
                return p
        return None


def _build_world(devices, n_workers=2, units=2, slowdowns=None,
                 num_microbatches=2):
    cfg = bert_config("tiny", dtype="float32", hidden_dropout_prob=0.0,
                      attention_probs_dropout_prob=0.0)
    mc = bert_layer_configs(cfg, num_encoder_units=units, num_classes=3,
                            deterministic=True)
    wm = WorkerManager()
    wm.load_worker_pool_from_config([
        dict(name=f"n{i}", device_config=dict(device_index=i),
             extra_config=dict(
                 slowdown=(slowdowns[i] if slowdowns else 1.0)))
        for i in range(n_workers)
    ])

    class _Dev:
        def benchmark(self):
            return {f"worker{w.rank}": dict(time=1.0, avai_mem=1e6)
                    for w in wm.worker_pool}

    class _Mod:
        def benchmark(self):
            return [1.0] * len(mc), [0.1] * len(mc)

    allocator = Allocator(mc, wm, _Mod(), _Dev())
    allocator.even_allocate()
    rng = np.random.default_rng(0)
    ids = rng.integers(5, 1024, size=(8, 16)).astype(np.int32)
    types, mask = np.zeros_like(ids), np.ones_like(ids)
    labels = rng.integers(0, 3, size=(8,)).astype(np.int32)
    ps = ParameterServer(mc, example_inputs=(ids, types, mask),
                         rng=jax.random.key(0))
    model = PipelineModel(wm, ps, _OPT, cross_entropy_loss,
                          devices=devices,
                          num_microbatches=num_microbatches)
    return model, allocator, wm, ps, (ids, types, mask), labels


class _Loader:
    def __init__(self, data, labels, n):
        self._batch, self._n = (data, labels), n

    def __iter__(self):
        for _ in range(self._n):
            yield self._batch

    def __len__(self):
        return self._n


def test_rejected_proposal_leaves_the_run_untouched(devices):
    """A proposal the pre-flight verifier rejects is never applied:
    the knob keeps its value and the signature is blocked."""
    model, allocator, wm, ps, data, labels = _build_world(devices)
    bad = Proposal(knob="microbatches", value=7, signature="bad_mb",
                   metric="step_p50_ms", reason="scripted")
    hook = AutotuneHook(advisor=_ScriptedAdvisor(bad), tune_every=2)
    runner = Runner(model, ps, wm, max_epochs=1, max_iters=6)
    runner.register_hook(hook)
    runner.train(_Loader(data, labels, 6))

    outcomes = [e["outcome"] for e in hook.events]
    assert "rejected" in outcomes
    assert "applied" not in outcomes
    assert model.num_microbatches == 2  # untouched
    assert "bad_mb" in hook.blocked
    rejected = next(e for e in hook.events if e["outcome"] == "rejected")
    assert "does not divide" in rejected["error"]


def test_failed_proposal_rolls_back_with_visible_spans(
    devices, monkeypatch
):
    """An applied proposal that does not improve the next window is
    rolled back — and the rollback is visible as spans + an async arc
    outcome in the trace."""
    import skycomputing_tpu.runner.hooks_collection.autotune_hook as mod

    monkeypatch.setattr(mod, "improved", lambda *a, **k: False)
    model, allocator, wm, ps, data, labels = _build_world(devices)
    assert model.schedule == "gpipe"
    flip = Proposal(knob="schedule", value="1f1b", signature="flip",
                    metric="step_p50_ms", reason="scripted")
    hook = AutotuneHook(advisor=_ScriptedAdvisor(flip), tune_every=2)
    tracer = telemetry.enable_tracing()  # hook joins, we keep the handle
    runner = Runner(model, ps, wm, max_epochs=1, max_iters=8)
    runner.register_hook(hook)
    runner.train(_Loader(data, labels, 8))

    outcomes = [e["outcome"] for e in hook.events]
    assert "applied" in outcomes
    assert "rolled_back" in outcomes
    assert model.schedule == "gpipe"  # reverted
    assert "flip" in hook.blocked

    events = tracer.to_chrome()["traceEvents"]
    names = [ev["name"] for ev in events if ev["ph"] == "X"]
    assert "autotune.apply" in names
    assert "autotune.rollback" in names
    arcs = [ev for ev in events if ev["ph"] == "e"
            and ev["name"] == "autotune"]
    assert arcs and arcs[-1]["args"]["outcome"] == "rolled_back"


def test_allocation_rejection_restores_partition_and_calibration(
    devices, monkeypatch
):
    """A re-solved allocation the plan verifier rejects must restore
    BOTH the partition and the allocator's learned calibration."""
    from skycomputing_tpu.analysis import plan_check

    model, allocator, wm, ps, data, labels = _build_world(
        devices, n_workers=2, units=2
    )
    before_partition = [list(w.model_config) for w in wm.worker_pool]
    before_calib = allocator.snapshot_calibration()

    def _veto(*args, **kwargs):
        from skycomputing_tpu.analysis.plan_check import (
            PlanIssue,
            PlanReport,
        )

        return PlanReport(issues=[
            PlanIssue("memory", "error", "scripted veto")
        ])

    monkeypatch.setattr(plan_check, "verify_plan", _veto)
    straggle = Proposal(knob="allocation", value=[0.3, 0.1],
                        signature=STRAGGLER, metric="step_p50_ms",
                        reason="scripted")
    hook = AutotuneHook(allocator=allocator,
                        advisor=_ScriptedAdvisor(straggle),
                        tune_every=2, solver_time_s=1.0)
    # the Runner's own preflight also routes through verify_plan; keep
    # the scripted veto scoped to the hook's verification call
    runner = Runner(model, ps, wm, max_epochs=1, max_iters=6,
                    preflight=False)
    runner.register_hook(hook)
    runner.train(_Loader(data, labels, 6))

    outcomes = [e["outcome"] for e in hook.events]
    assert "rejected" in outcomes and "applied" not in outcomes
    assert [list(w.model_config) for w in wm.worker_pool] == \
        before_partition
    assert allocator.snapshot_calibration() == before_calib
    assert STRAGGLER in hook.blocked


def test_allocator_calibration_snapshot_roundtrip(devices):
    _, allocator, wm, *_ = _build_world(devices)
    clean = allocator.snapshot_calibration()
    assert clean == {"cost": None, "speed": {}}
    allocator.calibrate_device_speeds([0.5, 0.1])
    dirty = allocator.snapshot_calibration()
    assert dirty["speed"]
    allocator.restore_calibration(clean)
    assert allocator.snapshot_calibration() == {"cost": None, "speed": {}}
    allocator.restore_calibration(dirty)
    assert allocator.snapshot_calibration() == dirty


# --------------------------------------------------------------------------
# E2E: straggler world converges, certified by trace_report --baseline
# --------------------------------------------------------------------------


@pytest.mark.chaos
# slow: the heaviest tune-suite test (~15 s: 3x-slowed worker, full
# AutotuneHook convergence + trace_report --baseline E2E).  The tier-1
# budget re-tier (870 s / 1-CPU host, >=15% headroom) moves it to the
# full run; the advisor/verify/rollback/reconfigure CONTRACTS stay
# tier-1 above.
@pytest.mark.slow
def test_autotuner_converges_on_straggler_world(devices, tmp_path):
    """The acceptance scenario: a 3x-slowed worker, no human in the
    loop — the tuner reads the trace, re-solves the allocation through
    the verifier, applies it via the rebuild path, and the post-tune
    trace beats the pre-tune operating point under the regression gate.
    """
    model, allocator, wm, ps, data, labels = _build_world(
        devices, n_workers=3, units=3, slowdowns=[3.0, 1.0, 1.0],
        num_microbatches=2,
    )
    even_partition = model.partition_signature()
    hook = AutotuneHook(allocator=allocator, tune_every=5,
                        min_improvement=0.02, solver_time_s=2.0)
    runner = Runner(model, ps, wm, max_epochs=1, max_iters=30)
    runner.register_hook(hook)
    runner.train(_Loader(data, labels, 30))

    applied = [e for e in hook.events if e["outcome"] == "applied"]
    assert applied, f"no proposal applied: {hook.events}"
    assert applied[0]["proposal"]["signature"] == STRAGGLER
    assert hook.tunes >= 1, f"nothing committed: {hook.events}"
    committed = [e for e in hook.events if e["outcome"] == "committed"]
    # the slow worker sheds layers (it started with an even share)
    new_partition = model.partition_signature()
    assert new_partition != even_partition
    slow_worker = next(w for w in wm.worker_pool
                       if w.extra_config.get("slowdown") == 3.0)
    slow_layers = len(slow_worker.model_config)
    assert slow_layers < max(len(w.model_config) for w in wm.worker_pool)

    # certification: a fresh traced run on the tuned plan must beat the
    # pre-tune operating point under the trace_report baseline gate
    from skycomputing_tpu.runner import TraceHook

    pre_tune_ms = applied[0]["base_ms"]
    post_tune_ms = committed[-1]["new_ms"]
    assert post_tune_ms < pre_tune_ms
    baseline = tmp_path / "pre_tune.json"
    baseline.write_text(json.dumps({"summary": {"step_ms": pre_tune_ms}}))

    trace_path = str(tmp_path / "tuned.trace.json")
    runner2 = Runner(model, ps, wm, max_epochs=1, max_iters=8)
    runner2.register_hook(TraceHook(trace_path))
    runner2.train(_Loader(data, labels, 8))
    assert report_main([trace_path, "--baseline", str(baseline)]) == 0


# --------------------------------------------------------------------------
# serving: reconfigure + ServingAutotuner
# --------------------------------------------------------------------------


def _gpt_world(buckets=(16,), num_slots=2, max_len=48, prefill_batch=1):
    from skycomputing_tpu.builder import build_layer_stack
    from skycomputing_tpu.models.gpt import GptConfig, gpt_layer_configs
    from skycomputing_tpu.serving import ServingEngine

    cfg = GptConfig(vocab_size=256, hidden_size=32, num_hidden_layers=2,
                    num_attention_heads=2, max_position_embeddings=64,
                    dropout_prob=0.0, dtype="float32")
    layer_cfgs = gpt_layer_configs(cfg, deterministic=True)
    stack = build_layer_stack(layer_cfgs)
    params = stack.init(jax.random.key(0), np.ones((1, 5), np.int32))
    # every test here means decode rows by num_slots
    engine = ServingEngine(layer_cfgs, list(params), num_slots=num_slots,
                           max_concurrency=num_slots,
                           max_len=max_len, buckets=buckets,
                           prefill_batch=prefill_batch)
    return engine, layer_cfgs, params


def _requests(lengths, max_new_tokens=4, seed=3):
    from skycomputing_tpu.serving import Request

    rng = np.random.default_rng(seed)
    return [
        Request(prompt=rng.integers(1, 256, (length,)).astype(np.int32),
                max_new_tokens=max_new_tokens)
        for length in lengths
    ]


def test_reconfigure_preserves_token_streams():
    """Mid-flight reconfiguration (new bucket set AND slot count) is
    token-identical to an untouched engine: evicted requests resume by
    recomputation, queued requests re-bucket."""
    engine_a, *_ = _gpt_world(buckets=(16,), num_slots=2)
    reqs_a = _requests([5, 9, 12, 7])
    expected = engine_a.run(reqs_a)

    engine_b, *_ = _gpt_world(buckets=(16,), num_slots=2)
    reqs_b = _requests([5, 9, 12, 7])
    for r in reqs_b:
        engine_b.submit(r)
    for _ in range(3):  # some running, some queued
        engine_b.step()
    engine_b.reconfigure(buckets=(8, 16), num_slots=4)
    assert engine_b.free_slots >= 2  # evicted + regrown pool
    got = engine_b.run()
    for req_a, req_b in zip(reqs_a, reqs_b):
        np.testing.assert_array_equal(
            expected[req_a.request_id], req_b.output()
        )
    # the new operating point is live
    assert engine_b.bucketer.buckets == (8, 16)
    assert engine_b.num_slots == 4
    assert len(got) >= 1


def test_reconfigure_rejects_infeasible_operating_points():
    from skycomputing_tpu.analysis.plan_check import PlanError

    engine, *_ = _gpt_world(buckets=(16,), num_slots=2, max_len=48)
    reqs = _requests([12, 9])
    for r in reqs:
        engine.submit(r)
    engine.step()
    # a bucket set the live requests cannot resume under
    with pytest.raises(ValueError, match="cannot resume"):
        engine.reconfigure(buckets=(8,))
    # a bucket past the slab depth fails the knob verifier
    with pytest.raises(PlanError):
        engine.reconfigure(buckets=(16, 64))
    with pytest.raises(PlanError):
        engine.reconfigure(num_slots=0)
    # malformed bucket entries reach the verifier as PlanIssues — never
    # a bare TypeError out of the normalization
    with pytest.raises(PlanError):
        engine.reconfigure(buckets=[16, None])
    # rejected reconfigures left the engine fully operational
    assert engine.bucketer.buckets == (16,)
    outputs = engine.run()
    assert len(outputs) == 2


def test_serving_autotuner_fixes_skewed_buckets(tmp_path):
    """E2E-lite: an engine mis-configured with one oversized bucket;
    the attached autotuner reads its own trace, proposes a tighter
    bucket, reconfigures, and commits after padding waste drops."""
    from skycomputing_tpu.tuning import ServingAutotuner

    engine, *_ = _gpt_world(buckets=(48,), num_slots=2, max_len=64)
    tuner = ServingAutotuner(engine, tune_every=10, max_tunes=2,
                             min_improvement=0.05)
    assert engine.autotuner is tuner
    tracer = telemetry.enable_tracing()
    try:
        lengths = [5, 7, 6, 9, 5, 8, 6, 7, 5, 6, 9, 7]
        outputs = engine.run(_requests(lengths, max_new_tokens=5))
        assert len(outputs) == len(lengths)
    finally:
        telemetry.disable_tracing()

    outcomes = [e["outcome"] for e in tuner.events]
    assert "applied" in outcomes, tuner.events
    assert "committed" in outcomes, tuner.events
    applied = next(e for e in tuner.events if e["outcome"] == "applied")
    assert applied["proposal"]["signature"] == SKEWED_BUCKETS
    # the tightened bucket is live and below the original
    assert min(engine.bucketer.buckets) < 48
    committed = next(e for e in tuner.events
                     if e["outcome"] == "committed")
    assert committed["new"] < committed["base"]
    # the loop is visible on the timeline
    events = tracer.to_chrome()["traceEvents"]
    names = {ev["name"] for ev in events if ev["ph"] in ("X", "i")}
    assert {"autotune.analyze", "autotune.apply", "reconfigure"} <= names


__all__ = []
