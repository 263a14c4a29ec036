"""The reduction from a trace to idle time by host span
(``harness/host_spans.py``): on hand-made events with known answers (two
chips, nested spans, a gap that straddles a span's end, a gap under no
span, a span on another thread), and on a few milliseconds saved from a
real traced run of cell 1 on the v5e (``host_spans_sample.json``: the
first 13 ms of one ``sky.pipe.step``, PR 26, operation names cut to 100
characters, times moved to start near 0).  Both are held to
``trace.reduce_events`` on the same events: the rows sum to the window
less the busy time."""

import json
import os

import pytest

from benchmarks.harness import host_spans, trace

HERE = os.path.dirname(os.path.abspath(__file__))
HOST, THREAD = "/host:CPU", "python3"


def _span(name, start, end, line=THREAD, **stats):
    return dict(plane=HOST, line=line, name=name, start_ns=start,
                dur_ns=end - start, stats=stats)


def _op(chip, start, end):
    return dict(plane=f"/device:TPU:{chip}", line=trace.OPS_LINE,
                name="%fusion", start_ns=start, dur_ns=end - start)


def _hand_made():
    return [
        _span(trace.MARK, 100, 1100),
        # nested as the program nests them; the outermost ends inside the
        # window, so its tail is under no span at all
        _span("sky.runner.iter", 50, 1000, iter=3),
        _span("sky.pipe.step", 200, 800),
        _span("sky.pipe.rng", 250, 400),
        _span("sky.pipe.fwd_issue", 400, 700),
        _span("sky.pipe.fwd", 450, 550, stage=0, mb=1),
        # another thread's span names no gap
        _span("sky.serve.step", 0, 2000, line="worker"),
        _op(0, 300, 350), _op(0, 380, 500), _op(0, 650, 900),
        _op(1, 100, 600),
        # other device lines repeat the same time and are not counted
        dict(plane="/device:TPU:0", line="Steps", name="7", start_ns=0,
             dur_ns=5000),
    ]


def test_hand_made_events_reduce_to_known_numbers():
    got = host_spans.reduce_events(_hand_made())
    ns = 1e-9
    assert got["thread"] == [HOST, THREAD]
    assert got["chips_traced"] == 2 and got["marks"] == 1
    assert got["window_s"] == pytest.approx(1000 * ns)
    # chip 0 is busy 420 of the 1000 ns and chip 1 500: their mean
    assert got["busy_s"] == pytest.approx(460 * ns)
    # chip 0's gap [500, 650) straddles the end of sky.pipe.fwd at 550;
    # both chips' last 100 ns lie after sky.runner.iter has closed
    assert got["idle_by_path"] == pytest.approx({
        "sky.runner.iter": 200 * ns,
        "sky.runner.iter/sky.pipe.step": 75 * ns,
        "sky.runner.iter/sky.pipe.step/sky.pipe.rng": 40 * ns,
        "sky.runner.iter/sky.pipe.step/sky.pipe.fwd_issue": 100 * ns,
        "sky.runner.iter/sky.pipe.step/sky.pipe.fwd_issue/sky.pipe.fwd":
            25 * ns,
        host_spans.OUTSIDE: 100 * ns,
    })
    assert got["idle_by_span"] == pytest.approx({
        "sky.runner.iter": 200 * ns, "sky.pipe.fwd_issue": 100 * ns,
        host_spans.OUTSIDE: 100 * ns, "sky.pipe.step": 75 * ns,
        "sky.pipe.rng": 40 * ns, "sky.pipe.fwd": 25 * ns,
    })
    assert list(got["idle_by_span"].values()) == sorted(
        got["idle_by_span"].values(), reverse=True)
    # spans: clipped to the window; self time is what no child covers
    rows = got["spans"]
    assert rows["sky.runner.iter"] == pytest.approx(
        dict(count=1, total_s=900 * ns, self_s=300 * ns))
    assert rows["sky.pipe.step"] == pytest.approx(
        dict(count=1, total_s=600 * ns, self_s=150 * ns))
    assert rows["sky.pipe.fwd_issue"] == pytest.approx(
        dict(count=1, total_s=300 * ns, self_s=200 * ns))
    assert rows["sky.pipe.fwd"] == pytest.approx(
        dict(count=1, total_s=100 * ns, self_s=100 * ns))
    assert "sky.serve.step" not in rows
    # only instances wholly inside the window have a duration to report
    assert "sky.runner.iter" not in got["durations"]
    assert got["durations"]["sky.pipe.rng"] == pytest.approx([150 * ns])


def test_rows_sum_to_window_less_busy_of_the_accepted_reducer():
    events = _hand_made()
    got = host_spans.reduce_events(events)
    accepted = trace.reduce_events(events)
    assert got["window_s"] == pytest.approx(accepted["window_s"])
    assert got["busy_s"] == pytest.approx(accepted["busy_s"])
    for table in ("idle_by_path", "idle_by_span"):
        assert sum(got[table].values()) == pytest.approx(
            accepted["window_s"] - accepted["busy_s"])
    assert got["idle_s"] == pytest.approx(
        accepted["window_s"] - accepted["busy_s"])


def test_the_metrics_questions():
    got = host_spans.reduce_events(_hand_made())
    # under an issue span or a child of one: rng 40 + fwd_issue 100 + fwd 25
    assert host_spans.idle_pct(got, under_any=host_spans.ISSUE) \
        == pytest.approx(16.5)
    # outside sky.pipe.step: the runner's own 200 and the 100 under no span
    assert host_spans.idle_pct(got, not_under=(host_spans.PIPE_STEP,)) \
        == pytest.approx(30.0)
    assert host_spans.idle_pct(got) == pytest.approx(54.0)
    # a step less the time inside its runs
    events = [
        _span("sky.serve.step", 0, 100), _span("sky.serve.run", 10, 40),
        _span("sky.serve.run", 50, 90),
        _span("sky.serve.step", 100, 300), _span("sky.serve.run", 120, 290),
        _span("sky.serve.step", 300, 400),  # ends after the window does
    ]
    assert host_spans.less_inside(
        events, "sky.serve.step", "sky.serve.run", window=(0, 350)
    ) == pytest.approx([30e-9, 30e-9])


def test_a_program_without_spans_reads_nothing():
    events = [e for e in _hand_made() if not e["name"].startswith("sky.")]
    assert trace.reduce_events(events) is not None
    assert host_spans.reduce_events(events) is None
    # no device operation: nothing either
    assert host_spans.reduce_events(
        [e for e in _hand_made() if e["plane"] == HOST]) is None


def test_a_reader_gets_nothing_from_an_untraced_run(monkeypatch):
    from benchmarks.harness import loading

    monkeypatch.setattr("sys.argv", ["run.py", "--workload", "some.cell"])
    assert host_spans.trace_dir_of_this_run() == os.path.join(
        loading.ROOT, ".bench_out", "some.cell", "trace")
    monkeypatch.setattr("sys.argv", ["run.py", "--workload=other"])
    assert host_spans.trace_dir_of_this_run().endswith(
        os.path.join(".bench_out", "other", "trace"))
    for name in ("idle_issue_pct.train", "idle_outside_step_pct.train",
                 "data_wait_ms.train", "tick_host_ms.serve",
                 "idle_engine_pct.serve", "idle_outside_step_pct.serve"):
        reader = loading.metric_reader("per_layer", name)
        for kind in ("train", "serve"):
            # untraced, or traced but with no trace directory to read
            assert reader.read(dict(kind=kind, trace=None)) is None
            assert reader.read(dict(kind=kind, trace=dict(busy_s=1.0))) \
                is None


def _sample():
    with open(os.path.join(HERE, "host_spans_sample.json")) as fh:
        return json.load(fh)


def test_real_sample_names_the_start_of_a_step():
    events = _sample()
    got = host_spans.reduce_events(events)
    accepted = trace.reduce_events(events)
    assert got["window_s"] == pytest.approx(accepted["window_s"])
    assert got["busy_s"] == pytest.approx(accepted["busy_s"])
    assert sum(got["idle_by_span"].values()) == pytest.approx(
        accepted["window_s"] - accepted["busy_s"])
    # the sample is cut from inside one step: every instant is under it
    assert host_spans.OUTSIDE not in got["idle_by_span"]
    assert all(path.startswith("sky.runner.iter/sky.pipe.step")
               for path in got["idle_by_path"])
    assert host_spans.idle_pct(got, not_under=(host_spans.PIPE_STEP,)) == 0
    # a step starts with the split and the input transfers, then the 16 rng
    # folds, 0.6 ms apart: the chip waits under those two names, all but
    # the little that lies between them
    assert list(got["idle_by_span"])[:2] == ["sky.pipe.rng",
                                             "sky.pipe.prefetch"]
    issue = host_spans.idle_pct(got, under_any=host_spans.ISSUE)
    assert 0.95 * host_spans.idle_pct(got) < issue <= host_spans.idle_pct(got)
    assert issue > 99.0  # the rng programs take 5 us each
    (runner_iter,) = [e for e in events if e["name"] == "sky.runner.iter"]
    assert set(runner_iter["stats"]) == {"iter"}
