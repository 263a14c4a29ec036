"""The serving engine's programs by the work they serve, in a reduced
trace (``trace.reduce_events``'s ``module_s``: device seconds in the
traced window by program name).

The engine names every program it dispatches with a prefix that says
which work it serves (``serving/engine.py``, the table "Program names"
in ``docs/observability.md``): ``prefill_`` (a prefill wave and its
first tokens), ``decode_`` (a decode tick and its next-token vector),
``spec_``, ``cow_``, ``swap_``; a program's module is ``jit_<name>``.
A program whose stage programs all carried one name (``jit_step``, and
``jit__lambda`` for its helpers) cannot be told apart by kind: there the
readers get ``None``, never a number."""

from __future__ import annotations

from typing import Optional

PREFILL = "jit_prefill_"
DECODE = "jit_decode_"
KINDS = (PREFILL, DECODE, "jit_spec_", "jit_cow_", "jit_swap_")
SERVE_DECODE = "sky.serve.decode"
SERVE_PREFILL = "sky.serve.prefill"


def device_s(trace: Optional[dict], prefix: str) -> Optional[float]:
    """Device seconds in the traced window of the programs whose module
    name starts with ``prefix``; ``None`` when no module of the trace
    carries a kind's prefix (a program that does not name its work)."""
    modules = (trace or {}).get("module_s") or {}
    if not any(name.startswith(KINDS) for name in modules):
        return None
    return sum(s for name, s in modules.items() if name.startswith(prefix))
