"""Nemotron-H family (Mamba-2 / dropless experts / GQA) at tiny widths on
the CPU, seeded weights, against the plain reference the benchmark keeps
(``benchmarks/reference/nemotron_h.py``): the mixers, the stack, the scan,
the share, the routing."""

import jax
import jax.numpy as jnp
import pytest

from benchmarks.reference import nemotron_h as ref
from nemotron_h_helpers import built, close, program_loss, tiny
from skycomputing_tpu.ops.moe_dropless import dropless_experts, route_top_k
from skycomputing_tpu.ops.ssd import ssd_scan


# -- (i) each mixer and the whole stack against the reference ---------------

@pytest.mark.parametrize("pattern", ["M", "E", "*", "ME*"])
def test_stack_matches_reference_forward_loss_and_gradients(pattern):
    cfg = tiny(pattern)
    stack, params, ids = built(cfg)
    logits = jax.jit(stack.apply)(params, ids)
    want = jax.jit(lambda p: jnp.stack(
        [ref.sequence_logits(p, row, ref._config_view(cfg)) for row in ids]
    ))(params)
    assert close(logits, want, 2e-5)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: program_loss(stack, p, ids)))(params)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.batch_loss(p, ids, cfg)))(params)
    assert abs(float(loss) - float(ref_loss)) <= 1e-5 * float(ref_loss)
    for got, exp in zip(jax.tree_util.tree_leaves(grads),
                        jax.tree_util.tree_leaves(ref_grads)):
        assert close(got, exp, 2e-4)


def test_bfloat16_stack_stays_near_the_float32_reference():
    cfg = tiny("ME*", dtype="bfloat16")
    stack, params, ids = built(cfg)
    loss = jax.jit(lambda p: program_loss(stack, p, ids))(params)
    ref_loss = jax.jit(lambda p: ref.batch_loss(p, ids, cfg))(params)
    assert abs(float(loss) - float(ref_loss)) <= 2e-2 * float(ref_loss)


# -- (ii) the chunked scan against the literal recurrence -------------------

@pytest.mark.parametrize("length", [64, 50, 16, 7])
def test_chunked_scan_equals_literal_recurrence(length):
    b, h, p, g, n = 2, 4, 8, 2, 16
    ks = jax.random.split(jax.random.key(length), 5)
    x = jax.random.normal(ks[0], (b, length, h, p))
    dt = 0.1 * jax.nn.softplus(jax.random.normal(ks[1], (b, length, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)))
    B = jax.random.normal(ks[3], (b, length, g, n))
    C = jax.random.normal(ks[4], (b, length, g, n))
    D = jnp.linspace(0.5, 1.5, h)

    def chunked(x, dt, A, B, C):
        return ssd_scan(x, dt, A, B, C, D, chunk=16, return_final_state=True)

    def literal(x, dt, A, B, C):
        ys, states = zip(*(ref.literal_scan(x[i], dt[i], A, B[i], C[i], D,
                                            scan_block=8) for i in range(b)))
        return jnp.stack(ys), jnp.stack(states)

    args = (x, dt, A, B, C)
    (y, state), (y_ref, state_ref) = (jax.jit(chunked)(*args),
                                      jax.jit(literal)(*args))
    assert close(y, y_ref, 1e-5) and close(state, state_ref, 1e-5)
    grads = jax.jit(jax.grad(lambda *a: (chunked(*a)[0] ** 2).sum(),
                             argnums=(0, 1, 2, 3, 4)))(*args)
    want = jax.jit(jax.grad(lambda *a: (literal(*a)[0] ** 2).sum(),
                            argnums=(0, 1, 2, 3, 4)))(*args)
    for got, exp in zip(grads, want):
        assert close(got, exp, 1e-5)


# -- (iii) the shares add up to the uncut layer -----------------------------

def test_shares_of_the_experts_add_up_to_the_uncut_layer():
    from skycomputing_tpu.models.nemotron_h import MoeMixer

    whole = tiny("E", experts_held_start=0, experts_held=8)
    x = jax.random.normal(jax.random.key(3), (2, 24, whole["hidden_size"]))
    mixer = MoeMixer(whole)
    params = mixer.init(jax.random.key(4), x)["params"]
    c = ref._config_view(whole)
    uncut = jax.jit(lambda p: ref.experts(
        p, x.reshape(-1, x.shape[-1]), c))(params)
    zero_shared = dict(params, shared_down=jnp.zeros_like(
        params["shared_down"]))
    total = None
    for start in range(0, 8, 2):   # four chips of two experts each
        share = tiny("E", experts_held_start=start, experts_held=2)
        held = dict(
            params if start == 0 else zero_shared,   # shared: counted once
            experts_up=params["experts_up"][start:start + 2],
            experts_down=params["experts_down"][start:start + 2],
        )
        part = jax.jit(MoeMixer(share).apply)({"params": held}, x)
        total = part if total is None else total + part
    assert close(total.reshape(uncut.shape), uncut, 2e-5)


# -- (iv) dropless at every load, and what it lowers to ----------------------

def dense_experts(tokens, idx, w, w_up, w_down, held_start):
    """Every held expert over every token, weighted where it was chosen."""
    want = jnp.zeros((tokens.shape[0], w_down.shape[2]))
    for e in range(w_up.shape[0]):
        weight = jnp.where(idx == held_start + e, w, 0.0).sum(1)
        want = want + weight[:, None] * (
            jnp.square(jax.nn.relu(tokens @ w_up[e])) @ w_down[e])
    return want


#: first choices ``[(expert, tokens) ...]`` of 256 tokens choosing 2 of 32
#: experts, of which 4..7 are held: a block is 128 rows of 512.  The other
#: tokens, and every second choice but ``every``'s, go to absent experts.
LOADS = {
    "no_pair_held": ([], 0),
    "under_one_block": ([(4, 60), (6, 40)], 1),
    "whole_blocks": ([(5, 128), (7, 128)], 2),
    "every_pair_held": (None, 4),
    "straddles_an_edge": ([(4, 20), (5, 200), (7, 10)], 2),
}


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("load", list(LOADS))
def test_dropless_equals_dense_experts_at_every_load(load, impl):
    from skycomputing_tpu.ops.moe_dropless import block_rows

    T, k, d, f, E, total, start = 256, 2, 32, 48, 4, 32, 4
    assert block_rows(T, k, E, total) == 128
    ks = jax.random.split(jax.random.key(11), 5)
    tokens = jax.random.normal(ks[0], (T, d))
    w_up = 0.1 * jax.random.normal(ks[1], (E, d, f))
    w_down = 0.1 * jax.random.normal(ks[2], (E, f, d))
    firsts, blocks = LOADS[load]
    t = jnp.arange(T)
    if firsts is None:
        idx = jnp.stack([start + t % E, start + (t + 1) % E], 1)
    else:
        first = jnp.full(T, 0)
        at = 0
        for expert, count in firsts:
            first = first.at[at:at + count].set(expert)
            at += count
        idx = jnp.stack([first, jnp.full(T, 9)], 1)
    idx = idx.astype(jnp.int32)
    w = jax.random.uniform(ks[3], (T, k), minval=0.2, maxval=1.5)
    g = jax.random.normal(ks[4], (T, d))
    held = int(((idx >= start) & (idx < start + E)).sum())
    assert -(-held // 128) == blocks

    def program(tokens, w, w_up, w_down):
        return dropless_experts(tokens, idx, w, w_up, w_down,
                                held_start=start, num_experts=total,
                                impl=impl)

    y, counts, walked = jax.jit(program)(tokens, w, w_up, w_down)
    assert int(walked) == blocks
    assert int(counts[-2]) == held and int(counts[-1]) == 0
    assert counts[:E].tolist() == [
        int((idx == start + e).sum()) for e in range(E)]
    assert close(y, dense_experts(tokens, idx, w, w_up, w_down, start), 2e-5)
    got = jax.jit(jax.grad(
        lambda *inputs: (program(*inputs)[0] * g).sum(),
        argnums=(0, 1, 2, 3)))(tokens, w, w_up, w_down)
    want = jax.grad(
        lambda *inputs: (dense_experts(inputs[0], idx, *inputs[1:], start)
                         * g).sum(),
        argnums=(0, 1, 2, 3))(tokens, w, w_up, w_down)
    for a, b in zip(got, want):
        if held:
            assert close(a, b, 2e-5)
        else:
            assert not a.any() and not b.any()


@pytest.mark.parametrize("T,total", [(64, 8), (256, 32)])
def test_dropped_counts_the_rows_the_grouped_product_did_not_write(
        monkeypatch, T, total):
    """``dropped_tokens`` is read off the product's result: a product that
    skips an expert's rows (here: the last held expert's) shows as that
    many pairs dropped, whatever ``group_sizes`` said it was given; in the
    one block that is the whole order (64 tokens) and in a loop's."""
    from skycomputing_tpu.ops import moe_dropless

    k, d, f, E = 2, 32, 48, 4
    ks = jax.random.split(jax.random.key(9), 4)
    tokens = jax.random.normal(ks[0], (T, d))
    idx, w = route_top_k(jax.random.normal(ks[1], (T, total)),
                         jnp.zeros(total), k, norm_topk_prob=True,
                         scaling_factor=2.5)
    w_up = 0.1 * jax.random.normal(ks[2], (E, d, f))
    w_down = 0.1 * jax.random.normal(ks[3], (E, f, d))
    real = moe_dropless.grouped_matmul

    def skips_the_last_expert(lhs, rhs, sizes, *, impl=None):
        return real(lhs, rhs, sizes.at[E - 1].set(0), impl=impl)

    _, sound, _ = dropless_experts(tokens, idx, w, w_up, w_down, held_start=2,
                                   num_experts=total, impl="xla")
    monkeypatch.setattr(moe_dropless, "grouped_matmul", skips_the_last_expert)
    _, faulty, _ = dropless_experts(tokens, idx, w, w_up, w_down,
                                    held_start=2, num_experts=total,
                                    impl="xla")
    assert int(sound[-1]) == 0 and int(sound[E - 1]) > 0
    assert int(faulty[-1]) >= int(sound[E - 1])
    assert int(faulty[-2]) == int(sound[-2])


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_dropless_under_skew_drops_nothing(impl):
    T, k, d, f, E, total = 128, 2, 32, 48, 4, 16
    ks = jax.random.split(jax.random.key(5), 4)
    tokens = jax.random.normal(ks[0], (T, d))
    logits = jax.random.normal(ks[1], (T, total))
    bias = jnp.zeros(total).at[5].set(10.0)     # nearly every token picks 5
    idx, w = route_top_k(logits, bias, k, norm_topk_prob=True,
                         scaling_factor=2.5)
    w_up = 0.1 * jax.random.normal(ks[2], (E, d, f))
    w_down = 0.1 * jax.random.normal(ks[3], (E, f, d))
    y, counts, blocks = dropless_experts(
        tokens, idx, w, w_up, w_down, held_start=4, num_experts=total,
        impl=impl)
    assert int(counts[1]) == T                   # expert 5 is local row 1
    held = (idx >= 4) & (idx < 8)
    assert int(counts[-2]) == int(held.sum()) and int(counts[-1]) == 0
    assert int(counts[:E].sum()) == int(held.sum())
    assert int(blocks) == 2                      # 128 rows a block, of 256
    assert close(y, dense_experts(tokens, idx, w, w_up, w_down, 4), 2e-5)


def test_expert_programs_hold_one_body_and_no_worst_case_buffer():
    """The expert part lowered for a TPU at the benchmark cell's shapes
    (nothing runs): the gradient program holds the six grouped products of
    ONE block (two recomputed, two for the rows, two transposed for the
    matrices) and the forward program two, neither a ``case`` (no second
    arm), neither an array of ``T x k`` rows of width ``d`` or ``f``."""
    T, k, d, f, E, total = 4096, 6, 2688, 1856, 8, 128
    shape = jax.ShapeDtypeStruct
    inputs = (shape((T, d), jnp.bfloat16), shape((T, k), jnp.float32),
              shape((E, d, f), jnp.float32), shape((E, f, d), jnp.float32))
    idx = shape((T, k), jnp.int32)

    def forward(idx, *inputs):
        tokens, *rest = inputs
        return dropless_experts(tokens, idx, *rest, held_start=0,
                                num_experts=total, impl="pallas")

    def gradient(idx, g, *inputs):
        _, pull = jax.vjp(lambda *x: forward(idx, *x)[0], *inputs)
        return pull(g)

    lowered = lambda fn, *args: jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    for text, products in (
            (lowered(forward, idx, *inputs), 2),
            (lowered(gradient, idx, shape((T, d), jnp.float32), *inputs), 6)):
        assert text.count("tpu_custom_call") == products
        assert "stablehlo.case" not in text
        assert "stablehlo.while" in text
        for width in (d, f):
            assert f"tensor<{T * k}x{width}x" not in text


def test_reference_step_by_layers_equals_its_value_and_grad():
    cfg = tiny("ME*")
    _, params, ids = built(cfg)
    stages = [params[:2], params[2:4], params[4:]]
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: ref.batch_loss([x for s in p for x in s], ids, cfg) / 2
    ))(stages)
    step = ref.make_reference_step(cfg, 2)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, stages)
    loss, grads = step(stages, zeros, ids)
    assert abs(float(loss) - float(want_loss)) <= 1e-6 * float(want_loss)
    for got, exp in zip(jax.tree_util.tree_leaves(grads),
                        jax.tree_util.tree_leaves(want)):
        assert close(got, exp, 1e-5)
