"""Layer: serving engine, scheduler (``serving/engine.py``,
``serving/batcher.py``).  Decode tokens over (engine iterations x decode
rows) in the window: the share of decode rows that did useful work."""


def read(record):
    if record.get("kind") != "serve":
        return None
    delta = record["stats_delta"]
    if not delta["iterations"]:
        return None
    return delta["decode_tokens"] / (delta["iterations"] * record["rows"]) \
        * 100.0
