"""Device time by pipeline stage, from the order in which the engine
issues its programs.

A trace names a program run by the jitted function (``jit_bwd(<n>)``), and
stages of one structure share a function, so the name does not say which
stage ran.  The order does: one chip runs its programs in the order the
host issued them, and gpipe's order is fixed (``parallel/pipeline.py``):
forward programs cycle through the stages 0..S-1 a microbatch; backward
programs (and the gradient accumulations that follow each) cycle S-1..0;
the updates go 0..S-1; the loss program belongs to the last stage.  A
stage that runs one program a layer (``LayeredStageRuntime``) takes as
many consecutive places in each cycle as it has layers.  The traced
sub-window opens and closes between two steps, with the chip drained, so
the first program of each kind in it is the first of a step.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from . import trace as trace_mod

FORWARD = ("jit_fwd", "jit_fwd_counted")
BACKWARD = ("jit_bwd", "jit_bwd_params_only")
ACCUMULATE = ("jit_grad_add",)
UPDATE = ("jit_update",)
LOSS = ("jit_loss_and_dlogits",)


def by_stage(module_events: List[dict],
             programs_a_stage: List[int]) -> Optional[List[float]]:
    """Seconds of device time a stage, from ``XLA Modules`` events (dicts
    with ``name``, ``start_ns``, ``dur_ns``) of ONE chip.
    ``programs_a_stage``: how many programs of each kind a stage issues a
    microbatch (1, or its number of layers)."""
    stages = len(programs_a_stage)
    place = [k for k, n in enumerate(programs_a_stage) for _ in range(n)]
    cycle = len(place)
    busy = [0.0] * stages
    seen: Dict[str, int] = {}
    for ev in sorted(module_events, key=lambda e: e["start_ns"]):
        name = trace_mod.module_name(ev["name"])
        for kinds, stage_of in (
            (FORWARD, lambda i: place[i % cycle]),
            (BACKWARD, lambda i: place[cycle - 1 - i % cycle]),
            (ACCUMULATE, lambda i: place[cycle - 1 - i % cycle]),
            (UPDATE, lambda i: place[i % cycle]),
            (LOSS, lambda i: stages - 1),
        ):
            if name in kinds:
                i = seen.get(kinds[0], 0)
                seen[kinds[0]] = i + 1
                busy[stage_of(i)] += ev["dur_ns"] / 1e9
                break
    return busy if any(busy) else None


def of_this_run(record: dict) -> Optional[List[float]]:
    from . import host_spans

    if not record.get("trace") or not record.get("programs_a_stage"):
        return None
    trace_dir = host_spans.trace_dir_of_this_run()
    if trace_dir is None:
        return None
    try:
        events = trace_mod.read_xplane(trace_mod.find_xplane(trace_dir))
    except FileNotFoundError:
        return None
    marks = [e for e in events if e["name"] == trace_mod.MARK]
    modules = [e for e in events
               if e["plane"].startswith(trace_mod.DEVICE_PLANE_PREFIX)
               and e["line"] == trace_mod.MODULES_LINE]
    if not marks or not modules:
        return None
    first = min(e["plane"] for e in modules)
    start = min(m["start_ns"] for m in marks)
    return by_stage([e for e in modules
                     if e["plane"] == first and e["start_ns"] >= start],
                    [int(n) for n in record["programs_a_stage"]])
