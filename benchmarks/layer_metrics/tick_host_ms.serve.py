"""Layer: serving engine, scheduler (``serving/engine.py``).  Median over
the traced ``sky.serve.step`` of its duration less the time inside
``sky.serve.run`` (dispatch through ``block_until_ready``, the only span in
which the chip is meant to be busy): the host work a serialized tick adds
to every token.  On the profiler's clock (``harness/host_spans.py``)."""

from benchmarks.harness import host_spans
from benchmarks.harness.stats import median


def read(record):
    if record.get("kind") != "serve":
        return None
    spans = host_spans.of_this_run(record)
    if spans is None:
        return None
    host = host_spans.less_inside(
        spans["events"], host_spans.SERVE_STEP, host_spans.SERVE_RUN,
        window=tuple(spans["window_ns"]))
    if not host:
        return None
    return median(host) * 1e3
