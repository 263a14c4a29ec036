"""Layer: kernels (``ops/moe_dropless.py``).  The grouped matrix product
over the held experts against its roofline: the least time the chip could
take for the grouped products the traced steps ran over the device time of
the custom calls under the ``moe_experts`` scope.  A microbatch runs eight
a layer (up and down, forward and recomputed; for each the rows' gradient
and the transposed product for the matrices'), each over the (token, held
expert) pairs the window REALLY routed here (the device's counters), each
reading every held expert's matrix once: at a sixteenth of the deployment's
load the bytes bound it."""

from benchmarks.harness import nemotron_h_counts as counts
from benchmarks.harness import scoped_ops
from benchmarks.harness.peaks import peaks_for


def read(record):
    if record.get("kind") != "train":
        return None
    scoped = scoped_ops.by_scope(record)
    if not scoped or not scoped["custom_call_seconds"].get("moe_experts"):
        return None
    c, mix = record["config"], record["traffic"]
    pairs = record["pairs_a_sequence_a_layer"] \
        * mix["batch_size"] / mix["microbatches"]
    shape = dict(pairs=pairs, d_in=c["hidden_size"],
                 d_out=c["moe_intermediate_size"])
    one = counts.least_seconds(
        counts.gmm_call_flops(**shape),
        counts.gmm_call_bytes(experts=c["n_routed_experts"], **shape),
        peaks_for(record["device_kind"]),
    )
    calls = (record["trace"]["marks"] * mix["microbatches"]
             * c["hybrid_override_pattern"].count("E") * 8)
    return calls * one / scoped["custom_call_seconds"]["moe_experts"] * 100.0
