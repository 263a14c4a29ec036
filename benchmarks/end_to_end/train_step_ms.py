"""Wall time per training step over ALL the steps and ALL the time of the
window: the window's length (its opening to the end of its last Runner
iteration) over the steps in it.  So it holds the data loader, the hooks
and the host loop, as a user's step does.  Host clock; the window spans
seconds, a single step well under the 250 ms a host stamp can resolve."""


def read(record):
    if record.get("kind") != "train" or not record.get("steps"):
        return None
    return record["window_s"] / record["steps"] * 1e3
