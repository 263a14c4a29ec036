"""Layer: model step, serving (``models/gpt.py`` decode program).  Blocked
wall time of the decode ticks (``ServingStats.decode_s``, timed by the
program across ``block_until_ready``) over the engine iterations of the
window."""


def read(record):
    if record.get("kind") != "serve":
        return None
    delta = record["stats_delta"]
    if not delta["iterations"]:
        return None
    return delta["decode_s"] / delta["iterations"] * 1e3
