"""Layer: model step, training.  Model FLOP/s utilization: the FLOPs the
forward and backward passes of one step require (from shapes, attention
included, recomputation not counted; ``harness/peaks.py``) times steps
per second over the chip's published bf16 peak.  An end-to-end
utilization, not a kernel's roofline share."""

from benchmarks.harness.peaks import peaks_for


def read(record):
    if record.get("kind") != "train" or not record.get("steps"):
        return None
    peak = peaks_for(record["device_kind"])["bf16_flops"]
    per_s = record["model_flops_per_step"] * record["steps"] \
        / record["window_s"]
    return per_s / peak * 100.0
