"""Layer: cache manager (``serving/paging.py``).  The highest
``ServingStats.pages_in_use`` seen after a tick of the window over
``num_pages``.  (Preemptions are printed beside it on an earlier line.)"""


def read(record):
    if record.get("kind") != "serve" or not record.get("num_pages"):
        return None
    return record["pages_in_use_peak"] / record["num_pages"] * 100.0
