"""The three readers of the serving model step's own device time and of
the prefill wave's padding (``prefill_device_pct.serve``,
``decode_device_ms.serve``, ``prefill_pad_pct.serve``), on a made-up
trace with known answers: one chip, the driver's marks around three
engine steps, the engine's named programs on ``XLA Modules`` and its
``sky.serve.*`` spans with their arguments.  And the cases in which each
must give ``None``: a program that names no work (``jit_step``,
``jit__lambda``), prefill spans without ``positions``, an untraced run,
a training record."""

import pytest

from benchmarks.harness import host_spans, loading, trace

HOST, THREAD = "/host:CPU", "python3"
CHIP = "/device:TPU:0"


def _span(name, start, end, **stats):
    return dict(plane=HOST, line=THREAD, name=name, start_ns=start,
                dur_ns=end - start, stats=stats)


def _program(name, start, end):
    """One program run: its ``XLA Modules`` event and one operation."""
    return [
        dict(plane=CHIP, line=trace.MODULES_LINE, name=f"{name}(4242)",
             start_ns=start, dur_ns=end - start),
        dict(plane=CHIP, line=trace.OPS_LINE, name="%fusion",
             start_ns=start, dur_ns=end - start),
    ]


def _events(prefill="jit_prefill_wave", first="jit_prefill_first_token",
            tick="jit_decode_tick", nxt="jit_decode_next",
            positions=True):
    """Window [1000, 4000): three steps of 1000 ns.  Step 1 prefills a
    wave of 100 true tokens in 128 positions, step 2 one of 60 in 256,
    each step ticks once.  A wave that starts before the window and one
    that ends after it are left out of the padding."""
    def wave(start, end, tokens, computed):
        stats = dict(bucket=computed, wave=1, tokens=tokens)
        if positions:
            stats["positions"] = computed
        return _span("sky.serve.prefill", start, end, **stats)

    events = [wave(500, 900, 10, 16)]  # before the window
    for i, (tokens, computed) in enumerate(((100, 128), (60, 256),
                                            (0, 0))):
        t = 1000 * (i + 1)
        events.append(_span(trace.MARK, t, t + 1000))
        events.append(_span("sky.serve.step", t, t + 1000, iter=i))
        if computed:
            events.append(wave(t + 10, t + 100, tokens, computed))
        events.append(_span("sky.serve.decode", t + 200, t + 300,
                            active=32))
    events.append(wave(3950, 4100, 7, 512))  # ends after the window
    # the chip: the step before the window's last tick runs into it (its
    # first 100 ns count), then per step a wave (300 ns) and its first
    # token (20), a tick (400) and its next-token vector (30); a
    # copy-on-write copy (50) in step 3
    events += _program(tick, 900, 1100)
    for i, has_wave in enumerate((True, True, False)):
        t = 1000 * (i + 1) + 100
        if has_wave:
            events += _program(prefill, t, t + 300)
            events += _program(first, t + 300, t + 320)
        events += _program(tick, t + 400, t + 800)
        events += _program(nxt, t + 800, t + 830)
    events += _program("jit_cow_copy_page", 3940, 3990)
    return events


def _record(events, kind="serve"):
    return dict(kind=kind, trace=trace.reduce_events(events))


@pytest.fixture
def spans_of(monkeypatch):
    """Hand a reader the host-span reduction of ``events`` as
    ``host_spans.of_this_run`` would (a traced run of this process)."""
    def use(events):
        reduced = host_spans.reduce_events(events)
        if reduced is not None:
            reduced["events"] = [
                e for e in events if e["name"].startswith(host_spans.PREFIX)
                and [e["plane"], e["line"]] == reduced["thread"]]
        monkeypatch.setattr(host_spans, "of_this_run",
                            lambda record: reduced if record.get("trace")
                            else None)
    return use


def _read(name, record):
    return loading.metric_reader("per_layer", name).read(record)


def test_known_answers(spans_of):
    events = _events()
    spans_of(events)
    record = _record(events)
    got = record["trace"]
    assert got["window_s"] == pytest.approx(3000e-9)
    # busy: 100 (the tick before) + 2 x (300 + 20) + 3 x (400 + 30) + 50
    assert got["busy_s"] == pytest.approx(2080e-9)
    assert _read("prefill_device_pct.serve", record) == pytest.approx(
        640 / 2080 * 100)
    # 100 + 3 x 430 ns of decode programs over three ticks
    assert _read("decode_device_ms.serve", record) == pytest.approx(
        1390e-9 / 3 * 1e3)
    # the two waves wholly inside the window: 160 true of 384 computed
    assert _read("prefill_pad_pct.serve", record) == pytest.approx(
        (1 - 160 / 384) * 100)


def test_a_program_that_names_no_work_reads_nothing(spans_of):
    """The parent's names: every stage program ``jit_step``, every helper
    ``jit__lambda``.  The device readers give ``None``, not 0; the
    padding, which the spans carry, does not depend on the names."""
    events = _events(prefill="jit_step", first="jit__lambda",
                     tick="jit_step", nxt="jit__lambda")
    events = [e for e in events if e["start_ns"] != 3940]  # no copy
    spans_of(events)
    record = _record(events)
    assert record["trace"]["module_s"].keys() == {"jit_step", "jit__lambda"}
    assert _read("prefill_device_pct.serve", record) is None
    assert _read("decode_device_ms.serve", record) is None
    assert _read("prefill_pad_pct.serve", record) == pytest.approx(
        (1 - 160 / 384) * 100)


def test_spans_without_positions_read_no_padding(spans_of):
    events = _events(positions=False)
    spans_of(events)
    record = _record(events)
    assert _read("prefill_pad_pct.serve", record) is None
    # the device readers do not need the argument
    assert _read("prefill_device_pct.serve", record) is not None


def test_a_window_without_prefill_reads_zero_device_and_no_padding(
        spans_of):
    """Named programs and no wave in the window: the chip spent none of
    it on prefill (0, a reading), and there is no padding to share out."""
    events = [e for e in _events()
              if e["name"] != "sky.serve.prefill"
              and not e["name"].startswith("jit_prefill_")]
    spans_of(events)
    record = _record(events)
    assert _read("prefill_device_pct.serve", record) == 0.0
    assert _read("prefill_pad_pct.serve", record) is None
    assert _read("decode_device_ms.serve", record) is not None


@pytest.mark.parametrize("name", ["prefill_device_pct.serve",
                                  "decode_device_ms.serve",
                                  "prefill_pad_pct.serve"])
def test_untraced_and_training_records_read_nothing(spans_of, name):
    events = _events()
    spans_of(events)
    assert _read(name, dict(kind="serve", trace=None)) is None
    assert _read(name, dict(kind="train",
                            trace=trace.reduce_events(events))) is None
    # traced, but the spans of the run could not be read: only the share
    # of busy time, which needs none, still reads
    spans_of([e for e in events if not e["name"].startswith("sky.")])
    value = _read(name, _record(events))
    assert (value is not None) == (name == "prefill_device_pct.serve")


def test_decode_ticks_are_counted_in_the_window(spans_of):
    """A tick's span half outside the window is clipped, not dropped (the
    count ``host_spans`` keeps); one wholly outside is not counted."""
    events = _events() + [
        _span("sky.serve.decode", 3990, 4050, active=32),
        _span("sky.serve.decode", 4100, 4200, active=32),
    ]
    spans_of(events)
    record = _record(events)
    assert _read("decode_device_ms.serve", record) == pytest.approx(
        1390e-9 / 4 * 1e3)
