"""Device time by ``jax.named_scope``, for a trace that names its events
by HLO instruction and by nothing else (the v5e's: looked at by hand, PR
34: an ``XLA Ops`` event carries the instruction's text and a duration,
no name stack).

The way back is the program's own: ``PipelineModel.scoped_instructions``
lists, for each stage program, which instruction lies under which scope
(from the ``op_name`` the compiler keeps on every instruction), and the
driver puts that list into the record.  ``harness/trace.py`` has already
summed the window's device time by ``<program>/<instruction text>``
(``op_time_by_name``); this file joins the two.  Two programs of one name
(three stages run a ``jit_bwd``) can hold an instruction of one name and
type under different scopes: such time is counted under NO scope and
reported as ``ambiguous_s``.
"""

from __future__ import annotations

import re
from typing import Dict, Optional

_EVENT = re.compile(r"^(%[\w.\-]+) = (\S+)")
_LAYOUT = re.compile(r"\{[^}]*\}")

_DONE: Dict[int, Optional[dict]] = {}


def _same_type(a: str, b: str) -> bool:
    """Result types as the two sides print them: layouts stripped, and the
    trace's side cut at 96 characters."""
    return a.startswith(b) or b.startswith(a)


def by_scope(record: dict) -> Optional[dict]:
    """``{"seconds": {scope: s}, "custom_call_seconds": {scope: s},
    "ambiguous_s": s}`` over the traced window, or ``None`` where there is
    no trace or the program lists no instructions (an earlier program)."""
    trace, rows = record.get("trace"), record.get("scoped_instructions")
    if not trace or not rows:
        return None
    key = id(trace)
    if key not in _DONE:
        _DONE[key] = _join(trace["op_time_by_name"], rows)
        from .runtime import emit

        emit(event="scoped_ops", **_DONE[key])
    return _DONE[key]


def _join(op_time_by_name: dict, rows) -> dict:
    listed: Dict[tuple, list] = {}
    for program, name, result, scope in rows:
        listed.setdefault((program, name), []).append(
            (_LAYOUT.sub("", result), scope))
    seconds: Dict[str, float] = {}
    custom: Dict[str, float] = {}
    ambiguous = 0.0
    for key, spent in op_time_by_name.items():
        program, _, text = key.partition("/")
        found = _EVENT.match(text)
        if not found:
            continue
        name, result = found.groups()
        scopes = {scope for typ, scope in listed.get((program, name), [])
                  if _same_type(typ, result)}
        if len(scopes) > 1:
            ambiguous += spent
            continue
        scope = scopes.pop() if scopes else ""
        if not scope:
            continue
        seconds[scope] = seconds.get(scope, 0.0) + spent
        if "custom-call" in text:
            custom[scope] = custom.get(scope, 0.0) + spent
    return dict(seconds=seconds, custom_call_seconds=custom,
                ambiguous_s=ambiguous)
