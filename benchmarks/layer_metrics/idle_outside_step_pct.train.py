"""Layer: runner + loader (``runner/runner.py``, the data loader, the
hooks).  Share of the traced window in which no operation ran on the chip
while the host was OUTSIDE ``sky.pipe.step``: in the loader's ``next``,
the hooks, the runner's log lines and loop, or under no span at all.  From
the profiler trace (``harness/host_spans.py``)."""

from benchmarks.harness import host_spans


def read(record):
    if record.get("kind") != "train":
        return None
    spans = host_spans.of_this_run(record)
    if spans is None:
        return None
    return host_spans.idle_pct(spans, not_under=(host_spans.PIPE_STEP,))
