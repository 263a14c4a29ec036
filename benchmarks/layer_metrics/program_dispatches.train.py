"""Layer: pipeline engine, host issue loop.  ``PipelineStats
.program_dispatches`` of a steady step: jitted programs the host called
in one step.  An exact count (68 in PR 21's smoke)."""

from benchmarks.harness.stats import median


def read(record):
    if record.get("kind") != "train" or not record.get("program_dispatches"):
        return None
    return median(record["program_dispatches"])
