"""Plain reference for a training step of the Nemotron-H stack: forward,
causal-LM loss and gradients in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.  No kernel, no chunking
identity, no grouped product, nothing imported from the program: the
Mamba-2 layer is the LITERAL recurrence over time (``lax.scan``, a step a
position), the expert layer a loop over the held experts with a mask,
attention a masked softmax over all keys.

It reads the program's own parameter trees (the list ``LayerStack.init``
gives: embedding, one tree a block, head), takes the same
``experts_held`` range and the same vocabulary slice, and leaves out what
the absent experts would add, as the program does.

From the published description (``config.json`` of
nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, the Mamba-2 paper):

    block   x = x + mixer(RMSNorm(x)), norm_eps 1e-5
    M       [z | xBC | dt] = x W_in; xBC = silu(conv1d_causal_depthwise(xBC));
            dt = softplus(dt + dt_bias); A = -exp(A_log);
            h_t = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t; y_t = h_t C_t + D x_t;
            y = RMSNorm_grouped(y * silu(z)); out = y W_out
    E       s = sigmoid(x W_g); top-k on s + bias; w = s[top] / sum * 2.5;
            sum over chosen AND held experts of w * W_down relu(W_up x)^2,
            plus the shared expert for every token
    *       softmax(q k^T / sqrt(d) + causal mask) v, 32 query heads on 2
            key/value heads, no rotary embedding
    loss    mean over positions of -log softmax(logits_t)[id_{t+1}]

Departures, none of which changes the arithmetic of a row:
  - a layer runs under ``jax.checkpoint`` (``make_reference_step`` takes
    the chain rule a layer at a time, which is the same thing) and the
    recurrence in checkpointed blocks of ``scan_block`` positions, so that
    the backward pass keeps one state a block and not one a position (2 MB
    each at the published widths: 4096 of them would be 8.6 GB a layer);
  - attention goes a block of queries at a time (every row still a full
    masked softmax over all keys);
  - ``rope_theta`` / ``partial_rotary_factor`` are not applied (the
    configuration file's ``assumed`` says why).
"""

from __future__ import annotations

import functools

# Stated tolerances of the first-step comparison, each between two readings
# on the chip at the timed sizes (PERF.md section 6, PR 34, has them all).
# Everything compared is read from the timed stage programs themselves.
#
# The program computes its matrix products on bfloat16 operands with
# float32 accumulation; the reference is float32 throughout.  Loss and
# gradient norms are sums over 16 thousand tokens and millions of
# parameters, so the roundings average out: they agree far more closely
# than any single activation does (read: loss 3.2e-6 to 7.0e-5, a stage's
# gradient norm 6.9e-5 to 6.0e-4), and they do not tell a bfloat16 scan
# state or router from a float32 one.  The two limits below them do.
LOSS_RTOL = 2e-4
GRAD_NORM_RTOL = 2e-3        # a pipeline stage's gradient norm
# The two mechanisms whose precision the configuration states apart from
# the matrix products, in every layer that has one, each on the input the
# stage program itself had (sown beside the result), so that lowering one
# fails by that limit alone:
# - the router's choices: float32 logits read 0.0 of 24,576 (token, choice)
#   pairs apart from a float64 host computation; bfloat16 operands (which is
#   also what a float32 product at the TPU's DEFAULT precision is) 2.0e-3,
#   bfloat16 throughout 4.5e-3; in the cell every E layer reads 0.0 on five
#   seeds, and 1.7e-3 to 2.6e-3 with the router on bfloat16 operands
ROUTER_CHOICE_MISMATCH = 5e-4
# - the scan's final state of a layer, relative L2 against the literal
#   recurrence over the inputs the stage program sowed: float32 chunk states
#   read 1.6e-5 to 1.8e-4 over the four layers and five seeds (the third M
#   layer 1.6e-4 to 1.8e-4 on every seed, the others under 6.4e-5);
#   bfloat16 chunk states 1.7e-3 to 1.9e-3 in every layer
SCAN_STATE_RTOL = 6e-4
# The first update: a parameter leaf's change against optax.adamw on the
# REFERENCE's gradients, |change - expected| / |expected|, the worst leaf.
# A state left unchanged reads 1, a layer updated with another's gradients
# 1.4.  Adam's first step is lr * g / (|g| + eps), the gradient's SIGN
# wherever |g| >> eps, so an element whose bfloat16 gradient falls on the
# other side of zero moves the other way by the whole step: the reading is
# at most 2 * sqrt(share of such elements).  Read: 0.55 to 0.58 over five
# seeds, always an expert layer's router, whose gradient is the faintest
# (8.5 to 9.8% of its elements move against the expected change, which the
# driver prints beside the reading; the head reads 0.12, the embedding
# 0.19).  That is rounding; the limit has the more room above the reading.
UPDATE_RTOL = 0.8


def _config_view(config: dict):
    """The keys the reference reads, under the published names."""
    c = dict(config)
    c.setdefault("experts_held_start", 0)
    c.setdefault("experts_held", c["n_routed_experts"])
    return c


def rms_norm(x, weight, eps, groups=1):
    import jax.numpy as jnp

    shape = x.shape
    x = x.reshape(*shape[:-1], groups, shape[-1] // groups)
    x = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x.reshape(shape) * weight


def literal_scan(x, dt, A, B, C, D, scan_block=128):
    """The recurrence as written, one position a step.  x [t, h, p], dt
    [t, h], A [h], B / C [t, g, n], D [h] -> (y [t, h, p], final state
    [h, p, n])."""
    import jax
    import jax.numpy as jnp

    t, h, p = x.shape
    g, n = B.shape[1], B.shape[2]
    Bh = jnp.repeat(B, h // g, axis=1)      # a group serves h // g heads
    Ch = jnp.repeat(C, h // g, axis=1)

    def position(state, inp):
        x_t, dt_t, B_t, C_t = inp
        state = (jnp.exp(dt_t * A)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
        y_t = jnp.einsum("hpn,hn->hp", state, C_t) + D[:, None] * x_t
        return state, y_t

    @jax.checkpoint
    def block(state, inp):
        return jax.lax.scan(position, state, inp)

    pad = (-t) % scan_block
    seqs = (x, dt, Bh, Ch)
    if pad:  # dt = 0 positions leave the state as it is
        seqs = tuple(jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
                     for a in seqs)
    seqs = tuple(a.reshape(-1, scan_block, *a.shape[1:]) for a in seqs)
    final, y = jax.lax.scan(block, jnp.zeros((h, p, n), x.dtype), seqs)
    return y.reshape(-1, h, p)[:t], final


def mamba_inputs(p, x, c):
    """What enters the scan: (z, x_s, dt, A, B, C) from the layer's normed
    input ``x`` [t, d]."""
    import jax
    import jax.numpy as jnp

    H, P = c["mamba_num_heads"], c["mamba_head_dim"]
    G, N, K = c["n_groups"], c["ssm_state_size"], c["conv_kernel"]
    inner = H * P
    t = x.shape[0]
    zxbcdt = x @ p["in_proj"]
    z = zxbcdt[:, :inner]
    xbc = zxbcdt[:, inner:2 * inner + 2 * G * N]
    dt = zxbcdt[:, 2 * inner + 2 * G * N:]
    padded = jnp.pad(xbc, [(K - 1, 0), (0, 0)])
    conv = p["conv_bias"] + sum(
        padded[j:j + t] * p["conv_weight"][j] for j in range(K))
    xbc = jax.nn.silu(conv)
    xs = xbc[:, :inner].reshape(t, H, P)
    B = xbc[:, inner:inner + G * N].reshape(t, G, N)
    C = xbc[:, inner + G * N:].reshape(t, G, N)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    return z, xs, dt, -jnp.exp(p["A_log"]), B, C


def mamba(p, x, c):
    import jax

    z, xs, dt, A, B, C = mamba_inputs(p, x, c)
    y, _ = literal_scan(xs, dt, A, B, C, p["D"],
                        c.get("scan_block", 128))
    y = y.reshape(x.shape[0], -1) * jax.nn.silu(z)
    y = rms_norm(y, p["norm_weight"], c["norm_eps"], groups=c["n_groups"])
    return y @ p["out_proj"]


def route(p, x, c):
    """(chosen experts [t, k], weights [t, k]) over ALL routed experts."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.sigmoid(x @ p["router"])
    _, idx = jax.lax.top_k(
        scores + jax.lax.stop_gradient(p["e_score_correction_bias"]),
        c["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, idx, axis=1)
    if c["norm_topk_prob"]:
        w = w / (w.sum(axis=1, keepdims=True) + 1e-20)
    return idx, w * c["routed_scaling_factor"]


def experts(p, x, c):
    import jax
    import jax.numpy as jnp

    idx, w = route(p, x, c)
    relu2 = lambda a: jnp.square(jax.nn.relu(a))
    shared = relu2(x @ p["shared_up"]) @ p["shared_down"]   # every token

    def one_more(out, expert):                              # the held only
        up, down, number = expert
        weight = jnp.where(idx == number, w, 0.0).sum(axis=1)
        return out + weight[:, None] * (relu2(x @ up) @ down), None

    held = c["experts_held_start"] + jnp.arange(c["experts_held"])
    out, _ = jax.lax.scan(one_more, shared,
                          (p["experts_up"], p["experts_down"], held))
    return out


def attention(p, x, c, query_block=512):
    import jax
    import jax.numpy as jnp

    Hq, Hkv, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    t = x.shape[0]
    q = (x @ p["q_proj"]).reshape(t, Hq, dh)
    k = jnp.repeat((x @ p["k_proj"]).reshape(t, Hkv, dh), Hq // Hkv, axis=1)
    v = jnp.repeat((x @ p["v_proj"]).reshape(t, Hkv, dh), Hq // Hkv, axis=1)
    block = min(query_block, t)
    pad = (-t) % block
    qb = jnp.pad(q, [(0, pad), (0, 0), (0, 0)]).reshape(-1, block, Hq, dh)
    starts = jnp.arange(qb.shape[0]) * block

    @jax.checkpoint
    def rows(args):
        q_rows, start = args
        scores = jnp.einsum("qhd,khd->hqk", q_rows, k) / jnp.sqrt(float(dh))
        seen = (start + jnp.arange(block))[:, None] >= jnp.arange(t)[None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    out = jax.lax.map(rows, (qb, starts)).reshape(-1, Hq * dh)[:t]
    return out @ p["o_proj"]


_MIXERS = {"M": mamba, "E": experts, "*": attention}


def sequence_logits(params, ids, c):
    """One sequence ``ids`` [t] through the whole stack -> logits [t, V]."""
    import jax

    pattern = c["hybrid_override_pattern"]
    x = params[0]["embedding"][ids]
    for kind, p in zip(pattern, params[1:-1]):
        def layer(p, x, kind=kind):
            normed = rms_norm(x, p["norm_weight"], c["norm_eps"])
            return x + _MIXERS[kind](p["mixer"], normed, c)

        x = jax.checkpoint(layer)(p, x)
    head = params[-1]
    return rms_norm(x, head["norm_weight"], c["norm_eps"]) @ head["lm_head"]


def causal_lm_loss(logits, ids):
    import jax
    import jax.numpy as jnp

    logp = jax.nn.log_softmax(logits[:-1], axis=-1)
    return -jnp.take_along_axis(logp, ids[1:, None], axis=1).mean()


def batch_loss(params, ids, config):
    """Mean over the batch's sequences of the causal-LM loss.  ``params``:
    the flat list of layer trees; ``ids`` [b, t]."""
    import jax
    import jax.numpy as jnp

    c = _config_view(config)
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), params)
        losses = [causal_lm_loss(sequence_logits(params, row, c), row)
                  for row in ids]
        return sum(losses) / len(losses)


def make_reference_step(config, num_microbatches: int):
    """``step(params_by_stage, grads_so_far, ids) -> (loss, grads)``: one
    microbatch's loss / M, and its gradients ADDED to ``grads_so_far``
    (both by stage, like ``params_by_stage``; the sums are made in place).

    The same mathematics as ``jax.value_and_grad(batch_loss)`` (a test
    holds the two together), taken a layer at a time so that it fits
    beside a model's resident state and compiles as one small program a
    layer kind: forward through the layers keeping each layer's input,
    then the chain rule backwards, each layer's ``jax.vjp`` recomputing
    its forward (what ``jax.checkpoint`` around a layer does)."""
    import jax
    import jax.numpy as jnp

    c = _config_view(config)
    pattern = c["hybrid_override_pattern"]
    f32 = lambda tree: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), tree)

    def highest(fn):
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)
        return run

    def block(kind):
        def layer(p, x):
            p = f32(p)
            normed = rms_norm(x, p["norm_weight"], c["norm_eps"])
            return x + _MIXERS[kind](p["mixer"], normed, c)
        return layer

    def head_loss(p, x, ids, scale):
        p = f32(p)
        logits = rms_norm(x, p["norm_weight"], c["norm_eps"]) @ p["lm_head"]
        return causal_lm_loss(logits, ids) * scale

    def add(so_far, grads):
        return jax.tree_util.tree_map(jnp.add, so_far, grads)

    def backward(layer):
        def run(p, x, dy, so_far):
            _, vjp = jax.vjp(layer, p, x)
            dp, dx = vjp(dy)
            return dx, add(so_far, dp)
        return jax.jit(highest(run), donate_argnums=(3,))

    embed = jax.jit(lambda p, ids: p["embedding"].astype(jnp.float32)[ids])

    @functools.partial(jax.jit, donate_argnums=(3,))
    def embed_backward(p, ids, dx, so_far):
        _, vjp = jax.vjp(lambda p: p["embedding"].astype(jnp.float32)[ids], p)
        return add(so_far, vjp(dx)[0])

    @functools.partial(jax.jit, donate_argnums=(4,))
    @highest
    def head_backward(p, x, ids, scale, so_far):
        loss, (dp, dx) = jax.value_and_grad(head_loss, argnums=(0, 1))(
            p, x, ids, scale)
        return loss, dx, add(so_far, dp)

    forward = {kind: jax.jit(highest(block(kind))) for kind in set(pattern)}
    back = {kind: backward(block(kind)) for kind in set(pattern)}

    def step(params_by_stage, so_far_by_stage, ids):
        sizes = [len(stage) for stage in params_by_stage]
        params = [p for stage in params_by_stage for p in stage]
        so_far = [g for stage in so_far_by_stage for g in stage]
        scale = 1.0 / (num_microbatches * len(ids))
        total = 0.0
        for row in jnp.asarray(ids):
            x = embed(params[0], row)
            inputs = []
            for kind, p in zip(pattern, params[1:-1]):
                inputs.append(x)
                x = forward[kind](p, x)
            loss, dx, so_far[-1] = head_backward(
                params[-1], x, row, scale, so_far[-1])
            total = total + loss
            for i in reversed(range(len(pattern))):
                dx, so_far[1 + i] = back[pattern[i]](
                    params[1 + i], inputs.pop(), dx, so_far[1 + i])
            so_far[0] = embed_backward(params[0], row, dx, so_far[0])
        cuts = [sum(sizes[:k]) for k in range(len(sizes) + 1)]
        return total, [so_far[a:b] for a, b in zip(cuts, cuts[1:])]

    return step
