"""Profiling / tracing helpers.

The reference's tracing story is wall-clock phase logging plus a shared-file
timer (SURVEY §5).  On TPU the native story is richer: ``jax.profiler``
traces (viewable in TensorBoard/Perfetto) plus XLA's per-executable cost
model.  These helpers wrap both behind a small API.
"""

from __future__ import annotations

import contextlib
from typing import Dict, Optional

import jax


@contextlib.contextmanager
def trace(log_dir: str):
    """Capture a jax.profiler trace of the enclosed block into ``log_dir``."""
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def annotate(name: str):
    """Named region that shows up on the profiler timeline."""
    return jax.profiler.TraceAnnotation(name)


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


__all__ = ["trace", "annotate", "compiled_cost"]
