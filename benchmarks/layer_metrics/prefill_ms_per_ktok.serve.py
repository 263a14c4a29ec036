"""Layer: model step, serving (prefill programs).  Blocked wall time of
the prefill waves in the window (``ServingStats.prefill_s``) per thousand
prompt tokens they computed."""


def read(record):
    if record.get("kind") != "serve":
        return None
    delta = record["stats_delta"]
    if not delta["prefill_tokens"]:
        return None
    return delta["prefill_s"] / delta["prefill_tokens"] * 1e6
