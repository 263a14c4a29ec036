"""From a traced run's ``.xplane.pb`` to what the host was doing while the
chip was idle, by the program's own spans.

The program opens a ``jax.profiler.TraceAnnotation`` named ``sky.<layer>.
<phase>`` around every host phase of a training step and of an engine
step whenever a profiler session runs (``skycomputing_tpu/telemetry/
tracer.py``; the catalogue is in ``docs/observability.md`` and
``PERF.md`` section 3).  They land on the host plane beside the driver's
``bench_iter`` marks, on the clock of the device planes' ``XLA Ops``, so
one file answers: while no operation ran on the chip, which span was the
innermost one open on the thread that issues the work?

Three steps, kept apart so that the arithmetic can be checked on a small
saved event list: ``read_xplane`` (file -> flat events, the layout of
``trace.read_xplane`` plus ``stats``), ``reduce_events`` (events -> the
tables below) and ``of_this_run`` (the traced run of THIS process, parsed
and reduced once, for the metric readers).  A program without such spans
(an older commit) gives ``None`` everywhere, never a number.

What ``reduce_events`` returns, times in seconds, the window being the
marks' span exactly as in ``trace.reduce_events``:

- ``spans``: per span name its ``count``, ``total_s`` and ``self_s``
  (duration less what its children cover), clipped to the window;
- ``durations``: per span name the durations of the instances that lie
  wholly inside the window;
- ``idle_by_path``: every instant in which no operation ran on the chip,
  put down to the stack of spans open at that instant
  (``"sky.runner.iter/sky.pipe.step/sky.pipe.rng"``), or to
  ``(outside)`` when none is; ``idle_by_span`` is the same summed by the
  innermost name.  The rows sum to ``window_s - busy_s``.
"""

from __future__ import annotations

import os
import sys
from typing import Dict, Iterable, List, Optional, Tuple

from . import trace as trace_mod

PREFIX = "sky."
OUTSIDE = "(outside)"

# the spans in which the host ISSUES a training step's work
# (``telemetry/analysis.py``'s ISSUE_SPANS, which this file may not import:
# the benchmark reads the program's trace, not the program)
ISSUE = ("sky.pipe.prefetch", "sky.pipe.rng", "sky.pipe.fwd_issue",
         "sky.pipe.bwd_issue", "sky.pipe.update_issue")
PIPE_STEP = "sky.pipe.step"
SERVE_STEP = "sky.serve.step"
SERVE_RUN = "sky.serve.run"
RUNNER_DATA = "sky.runner.data"


def read_xplane(path: str, op_names: bool = False) -> List[dict]:
    """The events the reducer reads, flat: the host planes' ``sky.*``
    spans (with their ``stats``) and the driver's marks, and the device
    planes' ``XLA Ops``.  An operation's name is its whole HLO line and a
    second of BERT-large training is a quarter of a million of them, so
    the names are left out unless asked for (a sample for the
    self-check)."""
    from jax.profiler import ProfileData

    events = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(trace_mod.DEVICE_PLANE_PREFIX)
        for line in plane.lines:
            if device:
                if line.name != trace_mod.OPS_LINE:
                    continue
                for ev in line.events:
                    events.append(dict(
                        plane=plane.name, line=line.name,
                        name=ev.name if op_names else "",
                        start_ns=int(ev.start_ns),
                        dur_ns=int(ev.duration_ns),
                    ))
                continue
            for ev in line.events:
                name = ev.name
                if name == trace_mod.MARK:
                    stats = {}
                elif name.startswith(PREFIX):
                    stats = {k: v for k, v in ev.stats}
                else:
                    continue
                events.append(dict(
                    plane=plane.name, line=line.name, name=name,
                    start_ns=int(ev.start_ns), dur_ns=int(ev.duration_ns),
                    stats=stats,
                ))
    return events


def _issuing_thread(events: List[dict]) -> Optional[Tuple[str, str]]:
    """The host thread whose spans name the gaps: the one the driver's
    marks are on (it calls ``train_step`` / ``engine.step()``), else the
    one with the most ``sky.*`` spans."""
    counts: Dict[Tuple[str, str], List[int]] = {}
    for ev in events:
        if ev["plane"].startswith(trace_mod.DEVICE_PLANE_PREFIX):
            continue
        row = counts.setdefault((ev["plane"], ev["line"]), [0, 0])
        row[0 if ev["name"] == trace_mod.MARK else 1] += 1
    if not counts:
        return None
    return max(counts, key=lambda key: tuple(counts[key]))


def _segments(spans: List[Tuple[int, int, str]], window: Tuple[int, int]
              ) -> List[Tuple[int, int, Tuple[str, ...]]]:
    """The window cut at every span's start and end: disjoint
    ``(start, end, stack of open span names)`` in time order, the stack
    empty where no span is open.  ``spans`` nest properly (they are
    context managers on one thread); one that straddles the parent's end
    by a clock tick is cut to it."""
    out: List[Tuple[int, int, Tuple[str, ...]]] = []
    stack: List[Tuple[int, str]] = []  # (end, name)
    cursor = window[0]

    def advance(to: int) -> None:
        nonlocal cursor
        to = min(to, window[1])
        if to > cursor:
            out.append((cursor, to, tuple(name for _, name in stack)))
            cursor = to

    for start, end, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        while stack and stack[-1][0] <= start:
            advance(stack[-1][0])
            stack.pop()
        advance(start)
        if stack:
            end = min(end, stack[-1][0])
        stack.append((end, name))
    while stack:
        advance(stack[-1][0])
        stack.pop()
    advance(window[1])
    return out


def _gaps(busy: List[Tuple[int, int]], window: Tuple[int, int]
          ) -> List[Tuple[int, int]]:
    """The window less the (merged, sorted) busy intervals."""
    out, cursor = [], window[0]
    for start, end in busy:
        start, end = max(start, window[0]), min(end, window[1])
        if end <= start:
            continue
        if start > cursor:
            out.append((cursor, start))
        cursor = max(cursor, end)
    if window[1] > cursor:
        out.append((cursor, window[1]))
    return out


def _overlap_by_stack(gaps: List[Tuple[int, int]],
                      segments: List[Tuple[int, int, Tuple[str, ...]]],
                      into: Dict[Tuple[str, ...], int]) -> None:
    """Both lists are disjoint and in time order: one pass."""
    i = 0
    for g0, g1 in gaps:
        while i < len(segments) and segments[i][1] <= g0:
            i += 1
        j = i
        while j < len(segments) and segments[j][0] < g1:
            s0, s1, stack = segments[j]
            lo, hi = max(g0, s0), min(g1, s1)
            if hi > lo:
                into[stack] = into.get(stack, 0) + hi - lo
            j += 1


def reduce_events(events: List[dict]) -> Optional[dict]:
    """See the module's docstring.  ``None`` when the trace holds no
    device operation or no ``sky.*`` span."""
    dev = trace_mod.DEVICE_PLANE_PREFIX
    marks = [e for e in events
             if e["name"] == trace_mod.MARK and not e["plane"].startswith(dev)]
    ops: Dict[str, List[Tuple[int, int]]] = {}
    for ev in events:
        if ev["plane"].startswith(dev) and ev["line"] == trace_mod.OPS_LINE:
            ops.setdefault(ev["plane"], []).append(
                (ev["start_ns"], ev["start_ns"] + ev["dur_ns"]))
    thread = _issuing_thread(events)
    spans = [
        (e["start_ns"], e["start_ns"] + e["dur_ns"], e["name"])
        for e in events
        if e["name"].startswith(PREFIX) and (e["plane"], e["line"]) == thread
    ]
    if not ops or not spans:
        return None
    if marks:
        window = (min(m["start_ns"] for m in marks),
                  max(m["start_ns"] + m["dur_ns"] for m in marks))
    else:
        every = [iv for ivs in ops.values() for iv in ivs]
        window = (min(s for s, _ in every), max(e for _, e in every))
    if window[1] <= window[0]:
        return None

    segments = _segments(spans, window)
    idle: Dict[Tuple[str, ...], int] = {}
    busy_ns = 0
    for intervals in ops.values():
        merged = trace_mod.merge_intervals(intervals)
        busy_ns += trace_mod.busy_ns(merged, window)
        _overlap_by_stack(_gaps(merged, window), segments, idle)
    chips = len(ops)

    table: Dict[str, Dict[str, float]] = {}
    durations: Dict[str, List[float]] = {}
    for start, end, name in spans:
        lo, hi = max(start, window[0]), min(end, window[1])
        if hi <= lo:
            continue
        row = table.setdefault(name, dict(count=0, total_s=0.0, self_s=0.0))
        row["count"] += 1
        row["total_s"] += (hi - lo) / 1e9
        if start >= window[0] and end <= window[1]:
            durations.setdefault(name, []).append((end - start) / 1e9)
    for start, end, stack in segments:
        if stack:
            table[stack[-1]]["self_s"] += (end - start) / 1e9

    idle_by_path = {
        "/".join(stack) if stack else OUTSIDE: ns / chips / 1e9
        for stack, ns in sorted(idle.items(), key=lambda kv: -kv[1])
    }
    idle_by_span: Dict[str, float] = {}
    for stack, ns in idle.items():
        leaf = stack[-1] if stack else OUTSIDE
        idle_by_span[leaf] = idle_by_span.get(leaf, 0.0) + ns / chips / 1e9
    return dict(
        window_ns=list(window),
        window_s=(window[1] - window[0]) / 1e9,
        busy_s=busy_ns / chips / 1e9,
        idle_s=sum(idle_by_path.values()),
        chips_traced=chips,
        marks=len(marks),
        thread=list(thread),
        spans=table,
        durations=durations,
        idle_by_path=idle_by_path,
        idle_by_span=dict(sorted(idle_by_span.items(),
                                 key=lambda kv: -kv[1])),
    )


# --- what the metric readers ask ---------------------------------------------

def idle_pct(reduced: dict, under_any: Iterable[str] = (),
             not_under: Iterable[str] = ()) -> float:
    """Idle time, in percent of the window, of the instants whose stack of
    open spans holds one of ``under_any`` (any stack, if empty) and none
    of ``not_under``."""
    under_any, not_under = set(under_any), set(not_under)
    seconds = 0.0
    for path, s in reduced["idle_by_path"].items():
        stack = set() if path == OUTSIDE else set(path.split("/"))
        if under_any and not stack & under_any:
            continue
        if stack & not_under:
            continue
        seconds += s
    return seconds / reduced["window_s"] * 100.0


def less_inside(events: List[dict], outer: str, inner: str,
                window: Optional[Tuple[int, int]] = None) -> List[float]:
    """For every ``outer`` span (wholly inside ``window``, if given) its
    duration less the time of the ``inner`` spans within it, seconds."""
    inners = sorted((e["start_ns"], e["start_ns"] + e["dur_ns"])
                    for e in events if e["name"] == inner)
    out = []
    for ev in events:
        if ev["name"] != outer:
            continue
        start, end = ev["start_ns"], ev["start_ns"] + ev["dur_ns"]
        if window and (start < window[0] or end > window[1]):
            continue
        inside = sum(min(e, end) - max(s, start) for s, e in inners
                     if e > start and s < end)
        out.append((end - start - inside) / 1e9)
    return out


# --- the traced run of this process ------------------------------------------

_RUN: Dict[str, Optional[dict]] = {}


def trace_dir_of_this_run() -> Optional[str]:
    """``<root>/.bench_out/<cell>/trace``: where ``run.py`` has the
    profiler write, the cell being the ``--workload`` of this process
    (a record handed to a reader does not hold the path)."""
    argv = sys.argv
    cell = None
    for i, word in enumerate(argv):
        if word == "--workload" and i + 1 < len(argv):
            cell = argv[i + 1]
        elif word.startswith("--workload="):
            cell = word.split("=", 1)[1]
    if cell is None:
        return None
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(root, ".bench_out", cell, "trace")


def of_this_run(record: dict) -> Optional[dict]:
    """The reduction of this process's traced run, with ``events`` (the
    host thread's spans) beside the tables; ``None`` for an untraced run,
    a rehearsal, or a program that opens no ``sky.*`` span.  Parsed and
    reduced once; the first call prints the ``host_spans`` line."""
    if not record.get("trace"):
        return None
    trace_dir = trace_dir_of_this_run()
    if trace_dir is None:
        return None
    if trace_dir not in _RUN:
        _RUN[trace_dir] = _reduce_dir(trace_dir)
    return _RUN[trace_dir]


def _reduce_dir(trace_dir: str) -> Optional[dict]:
    try:
        path = trace_mod.find_xplane(trace_dir)
    except FileNotFoundError:
        return None
    events = read_xplane(path)
    reduced = reduce_events(events)
    if reduced is None:
        return None
    reduced["events"] = [
        e for e in events
        if e["name"].startswith(PREFIX)
        and [e["plane"], e["line"]] == reduced["thread"]
    ]
    from .runtime import emit

    iters = max(reduced["marks"], 1)
    idle = reduced["idle_s"]
    emit(
        event="host_spans",
        window_s=reduced["window_s"], busy_s=reduced["busy_s"],
        marks=reduced["marks"], trace_bytes=os.path.getsize(path),
        host_events=len(reduced["events"]),
        device_events=sum(
            1 for e in events
            if e["plane"].startswith(trace_mod.DEVICE_PLANE_PREFIX)),
        idle_by_span=reduced["idle_by_span"],
        idle_by_path=reduced["idle_by_path"],
        span_self_ms_per_iter={
            name: row["self_s"] * 1e3 / iters
            for name, row in reduced["spans"].items()
        },
        span_total_ms_per_iter={
            name: row["total_s"] * 1e3 / iters
            for name, row in reduced["spans"].items()
        },
        idle_attributed_pct=(
            (1.0 - reduced["idle_by_span"].get(OUTSIDE, 0.0) / idle) * 100.0
            if idle > 0 else None
        ),
    )
    return reduced


def _main(argv: List[str]) -> int:
    """``python3 -m benchmarks.harness.host_spans <trace_dir> <out.json>
    [<span name> [<ms>]]``: the reduction of a trace for a look by hand,
    and a small sample for the self-check: the first ``ms`` milliseconds
    (default 6) of the second ``<span name>`` (default ``sky.pipe.step``)
    in the window, its spans and the operations in it, names cut to 100
    characters, times moved to start near 0."""
    import json

    events = read_xplane(trace_mod.find_xplane(argv[1]), op_names=True)
    reduced = reduce_events(events)
    name = argv[3] if len(argv) > 3 else PIPE_STEP
    span_ms = float(argv[4]) if len(argv) > 4 else 6.0
    sample: List[dict] = []
    picks = sorted((e for e in events if e["name"] == name),
                   key=lambda e: e["start_ns"])
    if len(picks) >= 2:
        lo = picks[1]["start_ns"]
        hi = lo + int(span_ms * 1e6)
        for ev in events:
            start, end = ev["start_ns"], ev["start_ns"] + ev["dur_ns"]
            if end <= lo or start >= hi or ev["name"] == trace_mod.MARK:
                continue
            start, end = max(start, lo), min(end, hi)
            cut = dict(ev, name=ev["name"][:100],
                       start_ns=start - lo + 1000, dur_ns=end - start)
            sample.append(cut)
        sample.insert(0, dict(
            plane=picks[1]["plane"], line=picks[1]["line"],
            name=trace_mod.MARK, start_ns=1000, dur_ns=hi - lo, stats={}))
    with open(argv[2], "w") as fh:
        json.dump(dict(reduced=reduced, sample=sample), fh)
    return 0


if __name__ == "__main__":
    sys.exit(_main(sys.argv))
