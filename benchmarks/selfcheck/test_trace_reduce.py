"""The reducer: on a few milliseconds of events saved from the first
real trace (``trace_sample.json``: the start of one BERT-large training
step on the v5e, PR 24, names cut to 100 characters, times moved to
start near 0), and on hand-made events that overlap."""

import json
import os

import pytest

from benchmarks.harness import trace

HERE = os.path.dirname(os.path.abspath(__file__))
DEV = "/device:TPU:0"


def _sample():
    with open(os.path.join(HERE, "trace_sample.json")) as fh:
        return json.load(fh)


def _op(name, start, dur, line=trace.OPS_LINE, plane=DEV):
    return dict(plane=plane, line=line, name=name, start_ns=start, dur_ns=dur)


def test_union_of_overlapping_intervals():
    spans = [(0, 10), (5, 20), (20, 25), (40, 50), (42, 45), (60, 60)]
    assert trace.merge_intervals(spans) == [(0, 25), (40, 50)]
    assert trace.busy_ns(spans) == 35
    assert trace.busy_ns(spans, window=(10, 45)) == 20


def test_hand_made_events_reduce_to_known_numbers():
    events = [
        _op(trace.MARK, 100, 1000, line="python3", plane="/host:CPU"),
        _op("jit_a(1)", 150, 500, line=trace.MODULES_LINE),
        _op("jit_b(2)", 700, 350, line=trace.MODULES_LINE),
        _op("%x = f32[8]{0} add(...)", 200, 100),
        _op("%y = f32[8]{0} mul(...)", 250, 150),   # overlaps %x
        _op("%x = f32[8]{0} add(...)", 500, 100),
        _op("%z = f32[8]{0} dot(...)", 700, 300),
        _op("%late = f32[8]{0} add(...)", 1050, 100),  # half outside
        _op("%dma = copy-start(...)", 0, 5000, line="Async XLA Ops"),
    ]
    got = trace.reduce_events(events)
    # busy: [200,400) + [500,600) + [700,1000) + [1050,1100) = 650
    assert got["window_s"] == pytest.approx(1000e-9)
    assert got["busy_s"] == pytest.approx(650e-9)
    assert got["marks"] == 1 and got["chips_traced"] == 1
    ops = dict(got["device_ops"])
    assert ops["jit_b/%z = f32[8] dot(...)"] == pytest.approx(300e-9)
    assert ops["jit_a/%x = f32[8] add(...)"] == pytest.approx(200e-9)
    assert got["device_ops"][0][0] == "jit_b/%z = f32[8] dot(...)"
    gaps = dict(got["idle_gaps"])
    # idle: 100 before the first op, 100 + 100 after jit_a's ops, 50
    # after jit_b's
    assert gaps["after:window_start"] == pytest.approx(100e-9)
    assert gaps["after:jit_a"] == pytest.approx(200e-9)
    assert gaps["after:jit_b"] == pytest.approx(50e-9)
    assert sum(gaps.values()) + got["busy_s"] == pytest.approx(
        got["window_s"])


def test_real_sample_idle_share_and_top_operations():
    events = _sample()
    got = trace.reduce_events(events)
    ops = [e for e in events if e["line"] == trace.OPS_LINE]
    mark = next(e for e in events if e["name"] == trace.MARK)
    assert got["window_s"] == pytest.approx(mark["dur_ns"] / 1e9)
    # in the real sample no two operations overlap: busy is their sum
    inside = [e for e in ops if e["start_ns"] >= mark["start_ns"]]
    assert got["busy_s"] == pytest.approx(
        sum(e["dur_ns"] for e in inside) / 1e9)
    idle = 1.0 - got["busy_s"] / got["window_s"]
    assert 0.99 < idle < 1.0  # a step starts with 16 tiny rng-fold programs
    assert all(name.startswith("jit__lambda/") for name, _ in
               got["device_ops"])
    assert got["device_ops"] == sorted(got["device_ops"],
                                       key=lambda kv: -kv[1])
    assert len(got["device_ops"]) <= 10 and len(got["idle_gaps"]) <= 10
    assert set(dict(got["idle_gaps"])) == {"after:window_start",
                                           "after:jit__lambda"}


def test_no_device_operation_reads_nothing():
    events = [_op(trace.MARK, 0, 100, line="python3", plane="/host:CPU")]
    assert trace.reduce_events(events) is None
