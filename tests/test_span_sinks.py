"""The one span primitive and its two sinks.

``telemetry.trace_span`` / ``telemetry.span_sinks().span`` feed the ring
(``enable_tracing()``) and, whenever a JAX profiler session is running,
a ``jax.profiler.TraceAnnotation`` of the catalogue name — so the
program's own phases land in the profiler's trace, on the device
operations' clock.  Pinned here, in tier-1 time and on the CPU:

- the primitive feeds both sinks, one only, or neither (and then is the
  shared no-op);
- under a real ``jax.profiler.start_trace`` the trainer and the serving
  engine leave exactly the catalogue's names in the ``.xplane.pb``,
  nested as ``docs/observability.md`` says, with their identifiers as
  stats;
- a session compiles nothing and dispatches nothing;
- ``telemetry/analysis.py`` reads the step's dispatch share from the
  issue spans.
"""

import glob
import os

import jax
import numpy as np
import pytest

from skycomputing_tpu import telemetry
from skycomputing_tpu.parallel.pipeline import xla_compile_count
from skycomputing_tpu.telemetry import Tracer, analysis
from skycomputing_tpu.telemetry import tracer as tracer_mod
from skycomputing_tpu.telemetry.tracer import _NULL_SINKS, _NULL_SPAN
from tests.test_pipeline import build_pipeline
from tests.test_telemetry import FakeClock, _Loader

pytestmark = pytest.mark.trace


@pytest.fixture(autouse=True)
def _no_leaked_tracer():
    telemetry.disable_tracing()
    yield
    telemetry.disable_tracing()


# --------------------------------------------------------------------------
# (a) the primitive: both sinks, one only, neither
# --------------------------------------------------------------------------


class _FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: same surface
    (``is_enabled``, construction with keyword stats, enter / exit), and
    a log of what was opened."""

    enabled = False
    log = []

    def __init__(self, name, **stats):
        self.name, self.stats = name, stats

    @classmethod
    def is_enabled(cls):
        return cls.enabled

    def __enter__(self):
        type(self).log.append(("enter", self.name, self.stats))
        return self

    def __exit__(self, *exc):
        type(self).log.append(("exit", self.name, self.stats))
        return False


@pytest.fixture
def annotation_slot():
    """The tracer's resolved-once profiler sink, put back afterwards."""
    saved = tracer_mod._ANNOTATION[0]
    yield tracer_mod._ANNOTATION
    tracer_mod._ANNOTATION[0] = saved


@pytest.fixture
def fake_profiler(annotation_slot):
    _FakeAnnotation.enabled = False
    _FakeAnnotation.log = []
    annotation_slot[0] = _FakeAnnotation
    return _FakeAnnotation


@pytest.mark.parametrize("ring", [False, True], ids=["ring_off", "ring_on"])
@pytest.mark.parametrize("profiler", [False, True],
                         ids=["profiler_off", "profiler_on"])
def test_primitive_feeds_the_sinks_that_are_on(fake_profiler, ring, profiler):
    fake_profiler.enabled = profiler
    tracer = telemetry.enable_tracing(clock=FakeClock()) if ring else None
    sinks = telemetry.span_sinks()
    args = {"stage": 1, "mb": 0, "requests": [7, 8]}
    span = telemetry.trace_span("sky.pipe.fwd", "stage 1 [cpu]", "dispatch",
                                args, ring="fwd")
    if not ring and not profiler:
        # neither sink: the shared no-ops, nothing allocated or recorded
        assert sinks is _NULL_SINKS and span is _NULL_SPAN
        assert sinks.span("x") is _NULL_SPAN and sinks.lane("p") is None
    else:
        assert sinks is not _NULL_SINKS and span is not _NULL_SPAN
    with span:
        with telemetry.trace_span("sky.pipe.wait", "host", "dispatch"):
            pass
    if ring:
        names = [ev[1] for ev in tracer.events()]
        # the ring keeps its short name and ALL the args, lists too
        assert names == ["sky.pipe.wait", "fwd"]
        assert tracer.events()[1][6] == args
    if profiler:
        # the profiler gets the catalogue name, nested as opened, and the
        # args a stat can hold
        assert [(kind, name) for kind, name, _ in fake_profiler.log] == [
            ("enter", "sky.pipe.fwd"), ("enter", "sky.pipe.wait"),
            ("exit", "sky.pipe.wait"), ("exit", "sky.pipe.fwd"),
        ]
        assert fake_profiler.log[0][2] == {"stage": 1, "mb": 0}
    else:
        assert fake_profiler.log == []


def test_sinks_are_looked_up_when_asked_not_when_imported(fake_profiler):
    """A profiler that starts later is seen by the next lookup, and one
    that stops is dropped by it: the switch is the session itself."""
    assert telemetry.span_sinks() is _NULL_SINKS
    fake_profiler.enabled = True
    live = telemetry.span_sinks()
    assert live is not _NULL_SINKS and live.tracer is None
    assert live.lane("serving", "engine") is None  # no ring, no lane
    with live.span("sky.serve.step", None, {"iter": 3}):
        pass
    assert fake_profiler.log[0] == ("enter", "sky.serve.step", {"iter": 3})
    fake_profiler.enabled = False
    assert telemetry.span_sinks() is _NULL_SINKS


def test_seconds_into_times_a_phase_with_no_sink_on(fake_profiler):
    """Set-up phases are logged whether or not anything records them."""
    seconds = {}
    with telemetry.trace_span("sky.launch.data", "launch", "setup",
                              seconds_into=seconds):
        pass
    assert list(seconds) == ["sky.launch.data"]
    assert 0.0 <= seconds["sky.launch.data"] < 1.0
    # with the ring on the same reads feed the ring
    clock = FakeClock()
    tracer = telemetry.enable_tracing(clock=clock)
    with telemetry.trace_span("sky.launch.allocate", "launch", "setup",
                              seconds_into=seconds):
        clock.t += 2.5
    assert seconds["sky.launch.allocate"] == pytest.approx(2.5)
    (event,) = tracer.events()
    assert event[1] == "sky.launch.allocate" and event[3] == 2.5e6


def test_without_jax_the_profiler_sink_is_absent(monkeypatch, annotation_slot):
    """``telemetry/`` imports and works where jax does not: the lookup is
    lazy and guarded, and resolves to "no such sink"."""
    import builtins

    real_import = builtins.__import__

    def no_jax(name, *args, **kwargs):
        if name == "jax" or name.startswith("jax."):
            raise ImportError("no jax here")
        return real_import(name, *args, **kwargs)

    annotation_slot[0] = tracer_mod._UNRESOLVED
    monkeypatch.setattr(builtins, "__import__", no_jax)
    assert telemetry.span_sinks() is _NULL_SINKS
    assert annotation_slot[0] is None  # resolved once
    assert telemetry.trace_span("a", "p") is _NULL_SPAN
    tracer = telemetry.enable_tracing()
    with telemetry.trace_span("a", "p"):
        pass
    assert tracer.event_count == 1


# --------------------------------------------------------------------------
# (b) under a real profiler session: the catalogue, nested, with stats
# --------------------------------------------------------------------------


def _start_trace(out_dir):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0   # the benchmark's own options:
    options.host_tracer_level = 1     # TraceMe / TraceAnnotation only
    jax.profiler.start_trace(str(out_dir), profiler_options=options)


def _sky_spans(out_dir):
    """The ``sky.*`` events of the trace under ``out_dir`` with the parent
    of each by nesting on its thread: ``[(name, stats, parent name)]``."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(
        str(out_dir), "plugins", "profile", "*", "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            events = sorted(
                (e for e in line.events if e.name.startswith("sky.")),
                key=lambda e: (e.start_ns, -e.duration_ns))
            stack = []
            for ev in events:
                while stack and ev.start_ns >= stack[-1][1]:
                    stack.pop()
                out.append((ev.name, dict(ev.stats),
                            stack[-1][0] if stack else None))
                stack.append((ev.name, ev.start_ns + ev.duration_ns))
    return out


TRAIN_PARENT = {
    "sky.runner.iter": {None},
    "sky.runner.data": {"sky.runner.iter"},
    "sky.runner.hooks": {"sky.runner.iter"},
    "sky.runner.log": {"sky.runner.iter"},
    "sky.runner.rng": {"sky.runner.iter"},
    "sky.runner.timer": {"sky.runner.iter"},
    "sky.pipe.step": {"sky.runner.iter"},
    "sky.pipe.prefetch": {"sky.pipe.step"},
    "sky.pipe.rng": {"sky.pipe.step"},
    "sky.pipe.fwd_issue": {"sky.pipe.step"},
    "sky.pipe.fwd": {"sky.pipe.fwd_issue"},
    "sky.pipe.bwd_issue": {"sky.pipe.step"},
    "sky.pipe.loss": {"sky.pipe.bwd_issue"},
    "sky.pipe.bwd": {"sky.pipe.bwd_issue"},
    "sky.pipe.update_issue": {"sky.pipe.step"},
    "sky.pipe.update": {"sky.pipe.update_issue"},
    "sky.pipe.wait": {"sky.pipe.step"},
    "sky.pipe.loss_get": {"sky.pipe.step"},
}


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_training_leaves_the_catalogue_in_the_profilers_trace(
        devices, tmp_path, schedule):
    from skycomputing_tpu.runner import Runner

    M, S, steps = 2, 2, 2
    model, data, labels, ps = build_pipeline(
        devices, n_workers=S, units=2, num_microbatches=M)
    model.schedule = schedule
    model.train_step(data, labels, rng=jax.random.key(0))  # compile first
    runner = Runner(model, ps, model._worker_manager, max_epochs=1,
                    max_iters=steps)
    _start_trace(tmp_path)
    try:
        runner.train(_Loader(data, labels, n=steps))
    finally:
        jax.profiler.stop_trace()

    spans = _sky_spans(tmp_path)
    assert {name for name, _, _ in spans} == set(TRAIN_PARENT)
    for name, _, parent in spans:
        assert parent in TRAIN_PARENT[name], (name, parent)
    by_name = {}
    for name, stats, _ in spans:
        by_name.setdefault(name, []).append(stats)
    # identifiers travel as stats: one fwd and one bwd per (stage, mb)
    cells = sorted((k, m) for k in range(S) for m in range(M)) * steps
    for name in ("sky.pipe.fwd", "sky.pipe.bwd"):
        assert sorted((s["stage"], s["mb"]) for s in by_name[name]) \
            == sorted(cells)
    assert sorted(s["mb"] for s in by_name["sky.pipe.loss"]) \
        == sorted(list(range(M)) * steps)
    assert sorted(s["stage"] for s in by_name["sky.pipe.update"]) \
        == sorted(list(range(S)) * steps)
    assert len(by_name["sky.pipe.step"]) == steps
    # the loader is asked once more than it gives: the fetch that finds
    # it exhausted is a (short) iteration of its own
    assert [s["iter"] for s in by_name["sky.runner.iter"]] \
        == list(range(steps)) + [steps]
    assert len(by_name["sky.runner.data"]) == steps + 1
    assert sorted(s["point"] for s in by_name["sky.runner.hooks"]) == sorted(
        ["before_train_iter", "after_train_iter"] * steps)
    assert len(by_name["sky.runner.log"]) == 2 * steps  # two lines a step
    assert len(by_name["sky.runner.rng"]) == steps
    assert len(by_name["sky.runner.timer"]) == 2 * steps  # around the step
    waits = sorted(s["what"] for s in by_name["sky.pipe.wait"])
    expect = ["bwd", "update"] + (["fwd"] if schedule == "gpipe" else [])
    assert waits == sorted(expect * steps)


SERVE_PARENT = {
    "sky.serve.step": {None},
    "sky.serve.admit": {"sky.serve.step"},
    "sky.serve.select_wave": {"sky.serve.admit"},
    "sky.serve.prefill": {"sky.serve.admit"},
    "sky.serve.decode": {"sky.serve.step"},
    "sky.serve.build": {"sky.serve.prefill", "sky.serve.decode"},
    "sky.serve.cow": {"sky.serve.prefill"},
    "sky.serve.run": {"sky.serve.prefill", "sky.serve.decode",
                      "sky.serve.retire"},
    "sky.serve.put": {"sky.serve.run"},
    "sky.serve.stage": {"sky.serve.run"},
    "sky.serve.retire": {"sky.serve.step"},
    "sky.serve.wait": {"sky.serve.run"},
    "sky.serve.commit": {"sky.serve.retire"},
    "sky.serve.sync": {"sky.serve.step"},
}


def _tiny_engine():
    from skycomputing_tpu.builder import build_layer_stack
    from skycomputing_tpu.models.gpt import GptConfig, gpt_layer_configs
    from skycomputing_tpu.serving import Request, ServingEngine

    cfg = GptConfig(vocab_size=256, hidden_size=32, num_hidden_layers=2,
                    num_attention_heads=2, max_position_embeddings=64,
                    dropout_prob=0.0, dtype="float32")
    layer_cfgs = gpt_layer_configs(cfg, deterministic=True)
    stack = build_layer_stack(layer_cfgs)
    params = stack.init(jax.random.key(0), np.ones((1, 5), np.int32))
    engine = ServingEngine(layer_cfgs, list(params), num_slots=3,
                           max_len=48, buckets=(8, 16), prefill_batch=2,
                           kv_layout="paged", page_size=8)
    rng = np.random.default_rng(9)

    def requests():
        return [
            Request(prompt=rng.integers(1, 256, (n,)).astype(np.int32),
                    max_new_tokens=6)
            for n in (14, 5)
        ]

    return engine, requests


def test_serving_leaves_the_catalogue_in_the_profilers_trace(tmp_path):
    engine, requests = _tiny_engine()
    engine.run(requests())  # compile every shape first
    for request in requests():
        engine.submit(request)
    first = engine.stats.iterations
    compiles0 = xla_compile_count()
    _start_trace(tmp_path)
    try:
        for _ in range(3):
            engine.step()
    finally:
        jax.profiler.stop_trace()
    assert xla_compile_count() == compiles0

    spans = _sky_spans(tmp_path)
    assert {name for name, _, _ in spans} == set(SERVE_PARENT)
    for name, _, parent in spans:
        assert parent in SERVE_PARENT[name], (name, parent)
    by_name = {}
    for name, stats, _ in spans:
        by_name.setdefault(name, []).append(stats)
    assert [s["iter"] for s in by_name["sky.serve.step"]] \
        == [first, first + 1, first + 2]
    # two prompts in two buckets: two waves in the first step, then three
    # decode ticks over both rows
    waves = sorted((s["bucket"], s["wave"], s["tokens"])
                   for s in by_name["sky.serve.prefill"])
    assert waves == [(8, 1, 5), (16, 1, 14)]
    assert all(s["shared"] == 0 for s in by_name["sky.serve.prefill"])
    assert [s["active"] for s in by_name["sky.serve.decode"]] == [2, 2, 2]
    # pages of 8; queries at 14, 15, 16 and 5, 6, 7, ten idle rows of
    # the program's twelve at a page each; the 16-bucket's 2 columns
    # until a row needs a third
    rows = engine.max_concurrency
    assert rows == 12
    assert [(s["attn_pages_live"], s["attn_pages_table"])
            for s in by_name["sky.serve.decode"]] \
        == [(2 + 1 + 10, rows * 2), (2 + 1 + 10, rows * 2),
            (3 + 1 + 10, rows * 4)]
    # the first tick has nothing of an earlier step in flight, the next
    # two have the tick before them
    assert [s["in_flight"] for s in by_name["sky.serve.decode"]] \
        == [0, 1, 1]
    assert all(s["stage"] == 0 for s in by_name["sky.serve.stage"])
    for name in ("sky.serve.put", "sky.serve.stage"):
        # one stage: one put and one dispatch to a program
        assert len(by_name[name]) == 5
    # steps 2 and 3 each read back the step before them (step 1's two
    # waves and its tick, then step 2's tick): one barrier and one
    # commit to a read-back
    assert [s["programs"] for s in by_name["sky.serve.retire"]] == [3, 1]
    for name in ("sky.serve.wait", "sky.serve.commit"):
        assert len(by_name[name]) == 2
    assert len(by_name["sky.serve.run"]) == 5 + 2
    assert len(by_name["sky.serve.sync"]) == 3


@pytest.mark.parametrize("chunk", [None, 8], ids=["tail_wave", "chunk_wave"])
def test_prefill_span_counts_the_positions_its_program_computes(
        tmp_path, chunk):
    """``sky.serve.prefill`` carries ``positions``: the ``[rows, bucket]``
    its program computes, pad rows and pad columns included, beside
    ``tokens``, the true ones; for a tail wave and for a chunk wave."""
    engine, requests = _tiny_engine()
    if chunk:
        engine.reconfigure(prefill_chunk=chunk)
    engine.run(requests())  # compile every shape first
    for request in requests():
        engine.submit(request)
    _start_trace(tmp_path)
    try:
        engine.run()
    finally:
        jax.profiler.stop_trace()
    waves = [stats for name, stats, _ in _sky_spans(tmp_path)
             if name == "sky.serve.prefill"]
    rows = engine.prefill_batch
    assert rows == 2
    for wave in waves:
        assert wave["positions"] == rows * wave["bucket"]
        assert wave["tokens"] <= wave["positions"]
        assert bool(wave.get("chunk")) == bool(chunk)
    # prompts of 14 and 5: one wave each in buckets 16 and 8, or chunks
    # of at most 8 (14 = 8 + 6) that pack two to a wave of bucket 8
    if chunk:
        assert sum(w["tokens"] for w in waves) == 14 + 5
        assert {w["bucket"] for w in waves} == {8}
        assert sorted((w["wave"], w["positions"]) for w in waves) \
            == [(1, 16), (2, 16)]
    else:
        assert sorted((w["bucket"], w["tokens"], w["positions"])
                      for w in waves) == [(8, 5, 16), (16, 14, 32)]


# --------------------------------------------------------------------------
# (c) a session changes nothing the program counts
# --------------------------------------------------------------------------


def test_a_profiler_session_compiles_and_dispatches_nothing(
        devices, tmp_path):
    model, data, labels, _ = build_pipeline(
        devices, n_workers=2, units=2, num_microbatches=2)
    for schedule in ("gpipe", "1f1b"):
        model.schedule = schedule
        model.train_step(data, labels, rng=jax.random.key(0))  # warm
        model.train_step(data, labels, rng=jax.random.key(1))
        quiet = model.stats
        compiles0 = xla_compile_count()
        _start_trace(tmp_path / schedule)
        try:
            model.train_step(data, labels, rng=jax.random.key(1))
        finally:
            jax.profiler.stop_trace()
        traced = model.stats
        assert xla_compile_count() == compiles0 and traced.compiles == 0
        assert traced.program_dispatches == quiet.program_dispatches
        assert traced.put_dispatches == quiet.put_dispatches
        assert traced.transfers == quiet.transfers


# --------------------------------------------------------------------------
# (d) analysis: the dispatch share from the issue spans
# --------------------------------------------------------------------------


def test_dispatch_share_is_the_union_of_the_issue_spans():
    """What ``analyze`` read from the one made-up ``host_dispatch`` span
    per step (its duration was ``dispatch_s``: the sum of the step's
    disjoint issue intervals) it now reads from those intervals
    themselves: the same total, the same share, the same step count."""
    clock = FakeClock()
    tracer = Tracer(clock=clock)
    host = tracer.lane("host", "dispatch")
    stage = tracer.lane("stage 0 [cpu:0]", "dispatch")
    step_s, issue_s = 0.100, {"sky.pipe.prefetch": 0.004,
                              "sky.pipe.rng": 0.006,
                              "sky.pipe.fwd_issue": 0.020,
                              "sky.pipe.bwd_issue": 0.030,
                              "sky.pipe.update_issue": 0.005}
    steps = 3
    for i in range(steps):
        clock.t = i * step_s
        with tracer.span("iter", tracer.lane("runner", "iterations")):
            with tracer.span("sky.pipe.step", host):
                for name, seconds in issue_s.items():
                    with tracer.span(name, host):
                        if name.endswith("_issue"):
                            # the per-stage span nests inside its loop
                            with tracer.span(name[9:-6], stage):
                                clock.t += seconds
                        else:
                            clock.t += seconds
                    clock.t += 0.002  # a barrier: not issue time
            clock.t = (i + 1) * step_s
    report = analysis.analyze(tracer.to_chrome()["traceEvents"])
    dispatch_s = sum(issue_s.values())  # PipelineStats.dispatch_s
    assert report["dispatch"]["steps"] == steps
    assert report["dispatch"]["total_ms"] == pytest.approx(
        steps * dispatch_s * 1e3)
    assert report["dispatch"]["share"] == pytest.approx(
        dispatch_s / step_s, abs=1e-4)


def test_ring_and_stats_agree_on_a_real_steps_dispatch(devices):
    """On a real step the issue spans' union is ``dispatch_s`` give or
    take the clock reads between them."""
    model, data, labels, _ = build_pipeline(
        devices, n_workers=2, units=2, num_microbatches=2)
    model.train_step(data, labels, rng=jax.random.key(0))  # warm
    tracer = telemetry.enable_tracing()
    try:
        model.train_step(data, labels, rng=jax.random.key(1))
    finally:
        telemetry.disable_tracing()
    events = tracer.to_chrome()["traceEvents"]
    names = {ev["name"] for ev in events if ev["ph"] == "X"}
    assert "host_dispatch" not in names
    assert set(analysis.ISSUE_SPANS) <= names
    # the ring keeps the short per-stage names analysis and tuning read
    assert {"fwd", "bwd", "update"} <= names
    issue_us = analysis.busy_us([
        (ev["ts"], ev["ts"] + ev["dur"]) for ev in events
        if ev["ph"] == "X" and ev["name"] in analysis.ISSUE_SPANS])
    assert issue_us / 1e6 == pytest.approx(model.stats.dispatch_s, rel=0.2)


def test_launcher_logs_its_set_up_phases():
    """``experiment.launch`` wraps its set-up blocks in ``sky.launch.*``
    spans whose seconds it logs itself, with no sink on."""
    import experiment.launch as launch

    seconds = {}
    with launch._phase("data", seconds):
        pass
    with launch._phase("allocate", seconds):
        pass
    assert list(seconds) == ["sky.launch.data", "sky.launch.allocate"]
    tracer = telemetry.enable_tracing()
    with launch._phase("build_pipeline", seconds):
        pass
    (event,) = tracer.events()
    assert event[1] == "sky.launch.build_pipeline"
