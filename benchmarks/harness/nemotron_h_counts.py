"""Operations and bytes of the Nemotron-H family, from shapes alone: what
``mfu_pct.train`` and the two roofline shares of the cell
``nemotron-3-nano-30b-a3b.train-b4s4096`` are divided into.

Matrix products count 2 FLOPs a multiply-add; a backward pass costs twice
its forward; recomputation (the engine rematerialises a stage inside its
backward program) is NOT model work.  Norms, activations, the depthwise
convolution (0.2% of an M layer), the embedding lookup and the optimizer
are left out.
"""

from __future__ import annotations


def ssd_scan_forward_flops(*, tokens: int, heads: int, head_dim: int,
                           groups: int, state: int, chunk: int) -> float:
    """The chunked scan's four products a sequence of ``tokens``: the
    ``[L, L]`` score block a group (C B^T), that block times x a head,
    the chunk states (x B^T) and the entering state's part (C h)."""
    scores = 2.0 * tokens * chunk * groups * state
    apply_block = 2.0 * tokens * chunk * heads * head_dim
    states = 2.0 * tokens * heads * head_dim * state
    entering = 2.0 * tokens * heads * head_dim * state
    return scores + apply_block + states + entering


def ssd_scan_forward_bytes(*, tokens: int, heads: int, head_dim: int,
                           groups: int, state: int,
                           compute_bytes: int = 2) -> float:
    """The least a forward scan moves: x, B, C read and y written in the
    compute dtype, dt read in float32.  (The backward reads these and
    dy and writes four gradients: twice this.)"""
    x_and_y = 2.0 * tokens * heads * head_dim * compute_bytes
    b_and_c = 2.0 * tokens * groups * state * compute_bytes
    return x_and_y + b_and_c + 4.0 * tokens * heads


def gmm_call_flops(*, pairs: float, d_in: int, d_out: int) -> float:
    """One grouped product over ``pairs`` (token, expert) rows."""
    return 2.0 * pairs * d_in * d_out


def gmm_call_bytes(*, pairs: float, experts: int, d_in: int, d_out: int,
                   compute_bytes: int = 2) -> float:
    """Every held expert's matrix once, the rows in and the rows out (the
    transposed product of the backward reads two row sets and writes the
    matrices: the same count)."""
    return compute_bytes * (experts * d_in * d_out
                            + pairs * (d_in + d_out))


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """Roofline: the larger of operations over peak and bytes over peak."""
    return max(flops / peaks["bf16_flops"],
               nbytes / peaks["hbm_bytes_per_s"])


def forward_flops_by_part(config: dict, *, seq: int,
                          pairs_a_layer: float) -> dict:
    """Forward FLOPs of ONE sequence of ``seq`` tokens through the stack
    the configuration file describes, by part.  ``pairs_a_layer``: (token,
    held expert) pairs an expert layer computes for that sequence."""
    c = config
    d = c["hidden_size"]
    H, P = c["mamba_num_heads"], c["mamba_head_dim"]
    G, N = c["n_groups"], c["ssm_state_size"]
    inner = H * P
    pattern = c["hybrid_override_pattern"]
    routed_total = c.get("n_routed_experts_published", c["n_routed_experts"])
    m_proj = 2.0 * seq * d * (2 * inner + 2 * G * N + H) \
        + 2.0 * seq * inner * d
    m_scan = ssd_scan_forward_flops(
        tokens=seq, heads=H, head_dim=P, groups=G, state=N,
        chunk=c["chunk_size"])
    e_router = 2.0 * seq * d * routed_total
    e_shared = 4.0 * seq * d * c["moe_shared_expert_intermediate_size"]
    e_routed = 2 * gmm_call_flops(pairs=pairs_a_layer, d_in=d,
                                  d_out=c["moe_intermediate_size"])
    q = c["num_attention_heads"] * c["head_dim"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    a_proj = 2.0 * seq * d * (q + 2 * kv) + 2.0 * seq * q * d
    a_scores = 2.0 * q * seq * seq     # QK^T and PV over the causal half
    n = {k: pattern.count(k) for k in "ME*"}
    return dict(
        mamba_projections=n["M"] * m_proj,
        ssd_scan=n["M"] * m_scan,
        router=n["E"] * e_router,
        shared_expert=n["E"] * e_shared,
        routed_experts=n["E"] * e_routed,
        attention_projections=n["*"] * a_proj,
        attention_scores=n["*"] * a_scores,
        head=2.0 * seq * d * c["vocab_size"],
    )


def train_step_flops(config: dict, *, batch: int, seq: int,
                     pairs_a_layer: float) -> float:
    """Model FLOPs of one training step: forward + backward (= 3 x
    forward) of ``batch`` sequences."""
    parts = forward_flops_by_part(config, seq=seq,
                                  pairs_a_layer=pairs_a_layer)
    return 3.0 * batch * sum(parts.values())
