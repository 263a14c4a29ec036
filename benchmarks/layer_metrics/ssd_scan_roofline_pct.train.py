"""Layer: kernels (``ops/ssd.py``).  The chunked scan against its
roofline: the least time the chip could take for the scans the traced
steps ran (the larger of operations over the bf16 peak and bytes over the
HBM peak, from shapes: ``harness/nemotron_h_counts.py``) over the device
time under the ``ssd_scan`` scope.  A microbatch runs a Mamba layer's scan
forward twice (the engine recomputes a stage inside its backward program)
and backward once; a backward is twice a forward in operations and bytes.
At the published widths the bytes bound it (0.10 ms a forward against 0.07
of operations)."""

from benchmarks.harness import nemotron_h_counts as counts
from benchmarks.harness import scoped_ops
from benchmarks.harness.peaks import peaks_for


def read(record):
    if record.get("kind") != "train":
        return None
    scoped = scoped_ops.by_scope(record)
    if not scoped or not scoped["seconds"].get("ssd_scan"):
        return None
    c, mix = record["config"], record["traffic"]
    shape = dict(heads=c["mamba_num_heads"], head_dim=c["mamba_head_dim"],
                 groups=c["n_groups"], state=c["ssm_state_size"])
    tokens = mix["seq_len"] * mix["batch_size"] // mix["microbatches"]
    forward = counts.least_seconds(
        counts.ssd_scan_forward_flops(tokens=tokens, chunk=c["chunk_size"],
                                      **shape),
        counts.ssd_scan_forward_bytes(tokens=tokens, **shape),
        peaks_for(record["device_kind"]),
    )
    calls = (record["trace"]["marks"] * mix["microbatches"]
             * c["hybrid_override_pattern"].count("M"))
    return calls * 4 * forward / scoped["seconds"]["ssd_scan"] * 100.0
