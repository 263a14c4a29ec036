"""Layer: serving engine, scheduler (``serving/engine.py``, the wave as
``_prefill_wave`` / ``_chunk_wave`` build it).  Share of the prefill
positions the chip computes that are padding: 1 - the true positions
(``tokens``) over the positions the programs compute (``positions``, the
wave's ``[rows, bucket]``), summed over the ``sky.serve.prefill`` spans
wholly inside the traced window.  A span without ``positions`` (a
program that does not count them) gives ``None``."""

from benchmarks.harness import host_spans, serve_programs


def read(record):
    if record.get("kind") != "serve":
        return None
    spans = host_spans.of_this_run(record)
    if spans is None:
        return None
    lo, hi = spans["window_ns"]
    tokens = positions = 0
    for ev in spans["events"]:
        if ev["name"] != serve_programs.SERVE_PREFILL:
            continue
        if ev["start_ns"] < lo or ev["start_ns"] + ev["dur_ns"] > hi:
            continue
        if "positions" not in ev["stats"]:
            return None
        tokens += ev["stats"]["tokens"]
        positions += ev["stats"]["positions"]
    if not positions:
        return None
    return (1.0 - tokens / positions) * 100.0
