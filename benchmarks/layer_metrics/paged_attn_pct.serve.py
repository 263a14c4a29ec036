"""Layer: kernels (``ops/paged_attention.py``).  Share of the device's
busy time spent in the paged-attention custom call, from the profiler
trace.  The v5e's trace prints the kernel as
``%GptBlock_Attn.decode_paged.<n> = ... custom-call(...)`` (looked at by
hand, PR 24): matched as a custom call with ``paged`` in its name.  A
share of time, not a roofline share: the kernel's operations and bytes
are not counted yet (that needs a ``name`` on the ``pallas_call``)."""


def read(record):
    trace = record.get("trace")
    if record.get("kind") != "serve" or not trace:
        return None
    kernel = sum(
        seconds for name, seconds in trace["op_time_by_name"].items()
        if "paged" in name and "custom-call" in name
    )
    if not kernel:
        return None  # no such name in this trace: leave the metric out
    return kernel / trace["busy_s"] * 100.0
