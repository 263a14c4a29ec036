"""Layer: runner + loader.  Median over the traced iterations of
``sky.runner.data``: the loader's ``next`` (the runner's fetch of the
batch), on the profiler's clock (``harness/host_spans.py``)."""

from benchmarks.harness import host_spans
from benchmarks.harness.stats import median


def read(record):
    if record.get("kind") != "train":
        return None
    spans = host_spans.of_this_run(record)
    waits = spans and spans["durations"].get(host_spans.RUNNER_DATA)
    if not waits:
        return None
    return median(waits) * 1e3
