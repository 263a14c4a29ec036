"""Layer: launcher + allocator (``dynamics/``).  Device time of the
heaviest logical pipeline stage's programs (forward, backward,
accumulation, update, loss) over the mean of the stages', in the traced
window: 1 is a partition that balances the stages, which is what the
allocator is for.  Programs are put down to stages by the engine's issue
order (``harness/stage_busy.py``)."""

from benchmarks.harness import stage_busy


def read(record):
    if record.get("kind") != "train":
        return None
    busy = stage_busy.of_this_run(record)
    if not busy:
        return None
    return max(busy) / (sum(busy) / len(busy))
