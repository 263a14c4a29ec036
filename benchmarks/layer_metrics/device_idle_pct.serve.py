"""Layer: device.  1 - (union of the intervals in which an operation ran
on the chip / the traced sub-window), from the profiler trace."""

from benchmarks.harness.trace import idle_pct


def read(record):
    if record.get("kind") != "serve":
        return None
    return idle_pct(record.get("trace"))
