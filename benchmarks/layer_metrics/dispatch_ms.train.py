"""Layer: pipeline engine, host issue loop (``parallel/pipeline.py``).
Median over the window's steps of ``PipelineStats.dispatch_s``: the wall
time the host spent ISSUING the step's programs before its barriers."""

from benchmarks.harness.stats import median


def read(record):
    if record.get("kind") != "train" or not record.get("dispatch_s"):
        return None
    return median(record["dispatch_s"]) * 1e3
