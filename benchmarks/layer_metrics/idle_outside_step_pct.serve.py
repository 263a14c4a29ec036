"""Layer: serving engine, scheduler, seen from its caller.  Share of the
traced window in which no operation ran on the chip while the caller was
BETWEEN two ``engine.step()`` calls (under no ``sky.serve.step``): what
the driver's own stamping, submitting and pumping costs.  From the
profiler trace (``harness/host_spans.py``)."""

from benchmarks.harness import host_spans


def read(record):
    if record.get("kind") != "serve":
        return None
    spans = host_spans.of_this_run(record)
    if spans is None:
        return None
    return host_spans.idle_pct(spans, not_under=(host_spans.SERVE_STEP,))
