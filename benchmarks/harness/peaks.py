"""One table of published peaks, and the counts of operations and bytes
that utilization numbers are divided into.

Peaks are per chip, keyed by ``jax.devices()[0].device_kind``.  A kind
that is not in the table is an error, never a default.
"""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e" (system architecture page):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": dict(
        bf16_flops=197e12, int8_ops=393e12, hbm_bytes_per_s=819e9,
        hbm_bytes=16e9,
        source="cloud.google.com/tpu/docs/v5e (TPU v5e, per chip)",
    ),
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add a row "
            f"with its source to benchmarks/harness/peaks.py"
        ) from None


def bert_train_step_flops(*, hidden_size: int, intermediate_size: int,
                          num_layers: int, batch: int, seq: int,
                          num_classes: int) -> float:
    """Model FLOPs of one training step (forward + backward) of a BERT
    classifier, from shapes alone.  Recomputation is NOT counted (the
    engine remats each stage; that work is overhead, not model work).

    Matrix multiplications: 2 FLOPs per multiply-add, and the backward
    pass costs twice the forward, so 6 x parameters x tokens for the
    encoder's dense layers (QKV + output projection 4h^2, MLP 2hi).
    Attention scores and context: QK^T and PV are 2 x s^2 x h each per
    sequence per layer forward, so 12 x s^2 x h with the backward.  The
    pooler (h^2) and classifier (h x C) see one token per sequence.
    Embedding lookups, LayerNorm, softmax, GELU and bias adds are left
    out (no multiply-adds on the MXU)."""
    tokens = batch * seq
    dense = num_layers * (
        4 * hidden_size * hidden_size + 2 * hidden_size * intermediate_size
    )
    attention = 12.0 * num_layers * batch * seq * seq * hidden_size
    head = 6.0 * batch * (
        hidden_size * hidden_size + hidden_size * num_classes
    )
    return 6.0 * dense * tokens + attention + head


def gpt_decode_tick_bytes(*, param_bytes: int, hidden_size: int,
                          num_layers: int, live_tokens: int,
                          kv_bytes_per_value: int) -> int:
    """Bytes one decode tick has to read at the least: every parameter
    once as it is stored, and the keys and values of every live token
    (2 x layers x hidden per token).  Activations and the one new row
    written are left out (a millionth of the rest)."""
    kv = 2 * num_layers * hidden_size * kv_bytes_per_value * live_tokens
    return int(param_bytes + kv)
