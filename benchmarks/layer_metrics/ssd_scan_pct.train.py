"""Layer: kernels (``ops/ssd.py``).  Share of the device's busy time spent
in operations under the ``ssd_scan`` scope (the chunked Mamba-2 scan,
forward, recomputed forward and backward), from the profiler trace joined
with the program's list of scoped instructions
(``harness/scoped_ops.py``).  A share of time; the roofline share is
``ssd_scan_roofline_pct.train``."""

from benchmarks.harness import scoped_ops


def read(record):
    if record.get("kind") != "train":
        return None
    scoped = scoped_ops.by_scope(record)
    if not scoped or not scoped["seconds"].get("ssd_scan"):
        return None
    return scoped["seconds"]["ssd_scan"] / record["trace"]["busy_s"] * 100.0
