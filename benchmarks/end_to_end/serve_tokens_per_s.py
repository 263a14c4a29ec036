"""Output tokens the engine emitted inside the window over the window's
length, under a standing backlog.  Tokens are counted by the driver's
own stamps (after each ``engine.step()``), the window by the host
clock."""


def read(record):
    if record.get("kind") != "serve" or not record.get("tokens_emitted"):
        return None
    return record["tokens_emitted"] / record["window_s"]
