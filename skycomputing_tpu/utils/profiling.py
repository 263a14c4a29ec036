"""XLA's per-executable cost model behind a small API.

Spans are not made here: the one span primitive is
``telemetry.trace_span`` / ``telemetry.span_sinks``, which feeds the
profiler's trace (``jax.profiler.TraceAnnotation``) whenever a profiler
session is running.
"""

from __future__ import annotations

from typing import Dict

import jax


def compiled_cost(fn, *args) -> Dict[str, float]:
    """XLA's cost model for jitted ``fn`` at these args: flops, bytes, time."""
    compiled = jax.jit(fn).lower(*args).compile()
    cost = compiled.cost_analysis()
    out = {
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "optimal_seconds": float(cost.get("optimal_seconds", 0.0)),
    }
    mem = compiled.memory_analysis()
    if mem is not None:
        out["argument_bytes"] = float(mem.argument_size_in_bytes)
        out["output_bytes"] = float(mem.output_size_in_bytes)
        out["temp_bytes"] = float(mem.temp_size_in_bytes)
    return out


__all__ = ["compiled_cost"]
