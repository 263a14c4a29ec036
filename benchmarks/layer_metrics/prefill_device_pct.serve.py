"""Layer: model step, serving (``models/gpt.py`` through the engine's
``prefill_wave`` program and its first-token programs).  Share of the
device's busy time in the traced window spent in the programs named
``prefill_*`` (``harness/serve_programs.py``): the chip's time on prefill,
during which every decode row waits.  From the profiler trace's
``XLA Modules``, as ``trace.reduce_events`` sums them."""

from benchmarks.harness import serve_programs


def read(record):
    trace = record.get("trace")
    if record.get("kind") != "serve" or not trace:
        return None
    seconds = serve_programs.device_s(trace, serve_programs.PREFILL)
    if seconds is None or not trace["busy_s"]:
        return None
    return seconds / trace["busy_s"] * 100.0
