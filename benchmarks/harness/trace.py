"""From a profiler trace to device busy/idle and the heaviest operations.

Two functions, kept apart so that the second can be checked on a small
saved event list: ``read_xplane`` (an ``.xplane.pb`` -> flat events) and
``reduce_events`` (events -> busy seconds, window, top operations).

What the v5e's trace looks like (looked at by hand, PR 24): one plane
per chip named ``/device:TPU:<n>``; on it the line ``XLA Ops`` holds one
event per executed HLO operation (fusions, custom calls, copies), under
the names the compiler gave them, and ``XLA Modules`` one per program
run.  Host threads sit on ``/host:CPU``; a ``TraceAnnotation`` made by
the driver shows there under its own name.  Other device lines (steps,
framework name scopes, DMA queues) repeat the same time under other
groupings and are not counted.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MARK = "bench_iter"  # the driver's TraceAnnotation around each iteration


def merge_intervals(spans: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Union of [start, end) intervals, sorted and disjoint.  (The
    arithmetic of ``telemetry/analysis.py``'s ``merge_intervals``.)"""
    merged: List[List[int]] = []
    for start, end in sorted(spans):
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def busy_ns(spans: Iterable[Tuple[int, int]],
            window: Optional[Tuple[int, int]] = None) -> int:
    total = 0
    for start, end in merge_intervals(spans):
        if window is not None:
            start, end = max(start, window[0]), min(end, window[1])
        total += max(end - start, 0)
    return total


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_xplane(path: str, everything: bool = False) -> List[dict]:
    """Events of the trace, flat.  Unless ``everything`` is asked for
    (a look by hand), only what the reducer reads is kept: the device
    planes' ``XLA Ops`` and ``XLA Modules`` lines and the driver's marks;
    a second of BERT-large training is a quarter of a million events."""
    from jax.profiler import ProfileData

    events = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        for line in plane.lines:
            if not everything and device and line.name not in (
                    OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if not everything and not device and ev.name != MARK:
                    continue
                events.append(dict(
                    plane=plane.name, line=line.name, name=ev.name,
                    start_ns=int(ev.start_ns), dur_ns=int(ev.duration_ns),
                ))
    return events


def summarize_lines(events: List[dict]) -> List[dict]:
    """Per (plane, line): how many events and how much time.  For a look
    at a trace by hand."""
    table: Dict[Tuple[str, str], List[int]] = {}
    for ev in events:
        row = table.setdefault((ev["plane"], ev["line"]), [0, 0])
        row[0] += 1
        row[1] += ev["dur_ns"]
    return [
        dict(plane=p, line=l, events=n, total_ms=ns / 1e6)
        for (p, l), (n, ns) in sorted(table.items())
    ]


def short_name(hlo: str, limit: int = 96) -> str:
    """An operation as the trace prints it is its whole HLO line, layouts
    and operands and all; keep its name, result type and opcode."""
    return re.sub(r"\{[^}]*\}", "", hlo)[:limit]


def module_name(text: str) -> str:
    """``jit_bwd(10970238497549016690)`` -> ``jit_bwd``: the fingerprint
    changes with every compile, the name does not."""
    return text.split("(", 1)[0]


def label_with_modules(ops: List[dict], modules: List[dict]) -> None:
    """Give every operation the program it ran in (``module`` key): the
    ``XLA Modules`` event whose interval holds the operation's start."""
    modules = sorted(modules, key=lambda m: m["start_ns"])
    starts = [m["start_ns"] for m in modules]
    for op in ops:
        i = bisect.bisect_right(starts, op["start_ns"]) - 1
        inside = i >= 0 and op["start_ns"] < (
            modules[i]["start_ns"] + modules[i]["dur_ns"])
        op["module"] = module_name(modules[i]["name"]) if inside else "?"


def idle_gaps_after(evs: List[dict], window: Tuple[int, int]) -> Dict[str, int]:
    """Idle nanoseconds inside ``window`` on one chip, summed by the
    program that ran last before each gap (``after:<module>``; the time
    before the first operation is ``after:window_start``).  What the host
    was doing in a gap is not known yet (no spans inside the program);
    the program the chip had just finished is."""
    out: Dict[str, int] = {}
    cursor, last = window[0], "window_start"
    for e in sorted(evs, key=lambda e: e["start_ns"]):
        start, end = e["start_ns"], e["start_ns"] + e["dur_ns"]
        if end <= window[0]:
            continue
        if start >= window[1]:
            break
        if start > cursor:
            key = f"after:{last}"
            out[key] = out.get(key, 0) + start - cursor
        if end > cursor:
            cursor, last = end, e.get("module", e["name"])
    if window[1] > cursor:
        key = f"after:{last}"
        out[key] = out.get(key, 0) + window[1] - cursor
    return out


def reduce_events(events: List[dict], top: int = 10) -> Optional[dict]:
    """Busy time, window and heaviest operations.

    The window is what the driver's marks cover on the trace's own clock
    (first mark's start to last mark's end); without marks it is the span
    of the device operations themselves.  Busy is the union of the
    intervals in which an operation ran, per chip, averaged over the
    chips that ran any.  Returns None when no device operation is there."""
    marks = [e for e in events
             if e["name"] == MARK
             and not e["plane"].startswith(DEVICE_PLANE_PREFIX)]
    ops: Dict[str, List[dict]] = {}
    modules: Dict[str, List[dict]] = {}
    for ev in events:
        if not ev["plane"].startswith(DEVICE_PLANE_PREFIX):
            continue
        if ev["line"] == OPS_LINE:
            ops.setdefault(ev["plane"], []).append(ev)
        elif ev["line"] == MODULES_LINE:
            modules.setdefault(ev["plane"], []).append(ev)
    for plane, evs in ops.items():
        label_with_modules(evs, modules.get(plane, []))
    if not ops:
        return None
    if marks:
        window = (min(m["start_ns"] for m in marks),
                  max(m["start_ns"] + m["dur_ns"] for m in marks))
    else:
        every = [e for evs in ops.values() for e in evs]
        window = (min(e["start_ns"] for e in every),
                  max(e["start_ns"] + e["dur_ns"] for e in every))
    if window[1] <= window[0]:
        return None
    busy = [
        busy_ns(((e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in evs),
                window)
        for evs in ops.values()
    ]
    by_name: Dict[str, int] = {}
    by_module: Dict[str, int] = {}
    for evs in ops.values():
        for e in evs:
            lo = max(e["start_ns"], window[0])
            hi = min(e["start_ns"] + e["dur_ns"], window[1])
            if hi > lo:
                key = f"{e['module']}/{short_name(e['name'])}"
                by_name[key] = by_name.get(key, 0) + hi - lo
                by_module[e["module"]] = by_module.get(e["module"], 0) \
                    + hi - lo
    gaps: Dict[str, int] = {}
    for evs in ops.values():
        for name, ns in idle_gaps_after(evs, window).items():
            gaps[name] = gaps.get(name, 0) + ns
    chips = len(ops)
    longest = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
    heaviest = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return dict(
        busy_s=sum(busy) / chips / 1e9,
        window_s=(window[1] - window[0]) / 1e9,
        chips_traced=chips,
        marks=len(marks),
        device_ops=[[name, ns / chips / 1e9] for name, ns in heaviest],
        idle_gaps=[[name, ns / chips / 1e9] for name, ns in longest],
        module_s={k: v / chips / 1e9 for k, v in sorted(
            by_module.items(), key=lambda kv: -kv[1])},
        op_time_by_name={k: v / chips / 1e9 for k, v in by_name.items()},
    )


def idle_pct(reduced: Optional[dict]) -> Optional[float]:
    """1 - busy / window of a reduced trace, in percent."""
    if not reduced:
        return None
    return (1.0 - reduced["busy_s"] / reduced["window_s"]) * 100.0


def _main(argv: List[str]) -> int:
    """``python3 -m benchmarks.harness.trace <trace_dir> <out_prefix>``:
    for a look at a trace by hand.  Writes the per-line summary, the
    heaviest names of every device line, and a small sample of events
    (for the self-check) as JSON."""
    import json

    events = read_xplane(find_xplane(argv[1]), everything=True)
    names: Dict[Tuple[str, str], Dict[str, List[int]]] = {}
    for ev in events:
        row = names.setdefault((ev["plane"], ev["line"]), {}) \
            .setdefault(ev["name"], [0, 0])
        row[0] += 1
        row[1] += ev["dur_ns"]
    top = {
        f"{p} | {l}": sorted(
            ([n, c, ns / 1e6] for n, (c, ns) in table.items()),
            key=lambda r: -r[2],
        )[:25]
        for (p, l), table in names.items()
    }
    marks = sorted((e for e in events if e["name"] == MARK),
                   key=lambda e: e["start_ns"])
    sample = []
    if len(marks) >= 3:
        lo = marks[1]["start_ns"]
        hi = marks[2]["start_ns"] + marks[2]["dur_ns"]
        sample = [e for e in events
                  if lo <= e["start_ns"] <= hi
                  and (e["name"] == MARK
                       or e["plane"].startswith(DEVICE_PLANE_PREFIX))]
    with open(argv[2] + "_summary.json", "w") as fh:
        json.dump(dict(lines=summarize_lines(events), top_names=top,
                       reduced=reduce_events(events)), fh, indent=1)
    with open(argv[2] + "_sample.json", "w") as fh:
        json.dump(sample, fh)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(_main(sys.argv))
