"""Process start to the first instant of the measured window: imports,
build, profile + allocate, compile or cache load, warm-up, and the
correctness reference where it runs before the window.  Host clock."""


def read(record):
    return record.get("setup_s")
