"""Layer: model step, serving (``models/gpt.py`` through the engine's
``decode_tick`` program and its next-token program).  Device milliseconds
of the programs named ``decode_*`` (``harness/serve_programs.py``) in the
traced window over the ``sky.serve.decode`` spans in it: the chip's time
a decode tick.  The chip runs about one step behind the host, so the tick
running as the window opens and the one queued as it closes trade places;
neither is the host's wait, which ``decode_tick_ms`` holds."""

from benchmarks.harness import host_spans, serve_programs


def read(record):
    trace = record.get("trace")
    if record.get("kind") != "serve" or not trace:
        return None
    seconds = serve_programs.device_s(trace, serve_programs.DECODE)
    if seconds is None:
        return None
    spans = host_spans.of_this_run(record)
    if spans is None:
        return None
    ticks = spans["spans"].get(serve_programs.SERVE_DECODE, {}).get("count")
    if not ticks:
        return None
    return seconds / ticks * 1e3
