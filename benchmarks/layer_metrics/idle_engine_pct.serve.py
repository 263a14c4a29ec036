"""Layer: serving engine, scheduler (``serving/engine.py``).  Share of the
traced window in which no operation ran on the chip while the host was
under ``sky.serve.step`` and NOT under ``sky.serve.run``: admission, the
numpy build before a dispatch, the commit after the barrier, the sync.
From the profiler trace (``harness/host_spans.py``)."""

from benchmarks.harness import host_spans


def read(record):
    if record.get("kind") != "serve":
        return None
    spans = host_spans.of_this_run(record)
    if spans is None:
        return None
    return host_spans.idle_pct(spans, under_any=(host_spans.SERVE_STEP,),
                               not_under=(host_spans.SERVE_RUN,))
