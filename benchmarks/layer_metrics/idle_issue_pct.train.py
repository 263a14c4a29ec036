"""Layer: pipeline engine, host issue loop (``parallel/pipeline.py``).
Share of the traced window in which no operation ran on the chip WHILE the
host was inside one of the step's issue spans (``sky.pipe.prefetch``,
``.rng``, ``.fwd_issue``, ``.bwd_issue``, ``.update_issue``, or a child of
these): the chip starved by the issue loop.  From the profiler trace
(``harness/host_spans.py``)."""

from benchmarks.harness import host_spans


def read(record):
    if record.get("kind") != "train":
        return None
    spans = host_spans.of_this_run(record)
    if spans is None:
        return None
    return host_spans.idle_pct(spans, under_any=host_spans.ISSUE)
