"""GPT family: causal masking, pipeline training, ring-attention variant."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

from skycomputing_tpu.builder import build_layer_stack
from skycomputing_tpu.models.gpt import (
    GptConfig,
    GptEmbeddings,
    causal_lm_loss,
    gpt_layer_configs,
)
from skycomputing_tpu.serving.kv_cache import decode_positions


def tiny_gpt(mesh=None, seq=32):
    cfg = GptConfig(vocab_size=512, hidden_size=64, num_hidden_layers=2,
                    num_attention_heads=2, max_position_embeddings=max(seq, 64),
                    dropout_prob=0.0, dtype="float32")
    return gpt_layer_configs(cfg, deterministic=True, mesh=mesh), cfg


def test_gpt_forward_and_causality():
    layer_cfgs, cfg = tiny_gpt()
    stack = build_layer_stack(layer_cfgs)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 512, (2, 32)).astype(np.int32)
    params = stack.init(jax.random.key(0), ids)
    logits = np.asarray(stack.apply(params, ids))
    assert logits.shape == (2, 32, 512)

    # causality: changing a future token must not affect earlier logits
    ids2 = ids.copy()
    ids2[:, 20:] = (ids2[:, 20:] + 7) % 512
    logits2 = np.asarray(stack.apply(params, ids2))
    np.testing.assert_allclose(logits[:, :20], logits2[:, :20],
                               rtol=1e-5, atol=1e-6)
    assert not np.allclose(logits[:, 20:], logits2[:, 20:])


def test_gpt_pipeline_trains(devices):
    from skycomputing_tpu.dynamics import (
        Allocator,
        ParameterServer,
        WorkerManager,
    )
    from skycomputing_tpu.parallel import PipelineModel

    layer_cfgs, cfg = tiny_gpt()
    wm = WorkerManager()
    wm.load_worker_pool_from_config(
        [dict(name=f"n{i}", device_config=dict(device_index=i),
              extra_config={}) for i in range(3)]
    )
    Allocator(layer_cfgs, wm, None, None).even_allocate()

    rng = np.random.default_rng(0)
    ids = rng.integers(0, 512, (4, 32)).astype(np.int32)
    ps = ParameterServer(layer_cfgs, example_inputs=(ids,))
    model = PipelineModel(wm, ps, optax.sgd(1e-2), causal_lm_loss,
                          devices=devices)
    # labels for a causal LM are the input ids themselves
    losses = [model.train_step((ids,), ids, rng=jax.random.key(i))
              for i in range(5)]
    assert losses[-1] < losses[0], losses


def test_gpt_ring_attention_matches_dense(devices):
    mesh = Mesh(np.array(devices), axis_names=("sp",))
    dense_cfgs, _ = tiny_gpt(mesh=None, seq=64)
    ring_cfgs, _ = tiny_gpt(mesh=mesh, seq=64)
    dense = build_layer_stack(dense_cfgs)
    ring = build_layer_stack(ring_cfgs)

    rng = np.random.default_rng(1)
    ids = rng.integers(0, 512, (2, 64)).astype(np.int32)
    params = dense.init(jax.random.key(0), ids)
    out_dense = np.asarray(dense.apply(params, ids))
    out_ring = np.asarray(ring.apply(params, ids))  # SAME params
    np.testing.assert_allclose(out_dense, out_ring, rtol=3e-4, atol=3e-5)


def test_generate_greedy_recovers_pattern(devices):
    from skycomputing_tpu.dynamics import (
        Allocator,
        ParameterServer,
        WorkerManager,
    )
    from skycomputing_tpu.models.gpt import generate
    from skycomputing_tpu.parallel import PipelineModel

    layer_cfgs, cfg = tiny_gpt()
    wm = WorkerManager()
    wm.load_worker_pool_from_config(
        [dict(name=f"n{i}", device_config=dict(device_index=i),
              extra_config={}) for i in range(2)]
    )
    Allocator(layer_cfgs, wm, None, None).even_allocate()
    pattern = np.tile(np.array([3, 7, 11, 5], np.int32), 8)[None].repeat(8, 0)
    ps = ParameterServer(layer_cfgs, example_inputs=(pattern,))
    model = PipelineModel(wm, ps, optax.adam(3e-3), causal_lm_loss,
                          devices=devices)
    for i in range(50):
        model.train_step((pattern,), pattern, rng=jax.random.key(i))

    out = generate(lambda ids: model.forward((ids,)),
                   np.array([3, 7], np.int32), max_new_tokens=6,
                   context_length=32)
    assert out[0].tolist() == [3, 7, 11, 5, 3, 7, 11, 5]

    with pytest.raises(ValueError, match="exceed"):
        generate(lambda ids: model.forward((ids,)),
                 np.arange(30, dtype=np.int32), 6, 32)


def test_generate_cached_matches_full_forward():
    from skycomputing_tpu.models.gpt import generate, generate_cached

    layer_cfgs, cfg = tiny_gpt()
    stack = build_layer_stack(layer_cfgs)
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, 512, (2, 5)).astype(np.int32)
    params = stack.init(jax.random.key(7), prompt)

    fwd = jax.jit(lambda ids: stack.apply(params, ids))

    # greedy: token-identical
    full = generate(fwd, prompt, max_new_tokens=9, context_length=32)
    cached = generate_cached(stack, params, prompt, max_new_tokens=9,
                             context_length=32)
    np.testing.assert_array_equal(full, cached)

    # sampled: same rng split sequence -> same tokens
    full_s = generate(fwd, prompt, max_new_tokens=9, context_length=32,
                      temperature=0.8, rng=jax.random.key(11))
    cached_s = generate_cached(stack, params, prompt, max_new_tokens=9,
                               context_length=32, temperature=0.8,
                               rng=jax.random.key(11))
    np.testing.assert_array_equal(full_s, cached_s)

    # single-new-token edge (scan length 0)
    full_1 = generate(fwd, prompt, max_new_tokens=1, context_length=32)
    cached_1 = generate_cached(stack, params, prompt, max_new_tokens=1,
                               context_length=32)
    np.testing.assert_array_equal(full_1, cached_1)

    # zero-token edge: both return the prompt unchanged
    np.testing.assert_array_equal(
        generate_cached(stack, params, prompt, 0, 32), prompt
    )

    # the compiled program is cached on the stack, not rebuilt per call
    assert len(stack._decode_programs) >= 2  # decoder + >=1 program
    before = dict(stack._decode_programs)
    generate_cached(stack, params, prompt, max_new_tokens=9,
                    context_length=32)
    assert stack._decode_programs == before


def test_gpt_profiles_through_model_benchmarker():
    from skycomputing_tpu.dataset import BaseGenerator
    from skycomputing_tpu.dynamics import ModelBenchmarker

    layer_cfgs, cfg = tiny_gpt()

    class IdGen(BaseGenerator):
        def generate(self):
            return np.ones((2, 32), np.int32)

    flops, mem = ModelBenchmarker(layer_cfgs, IdGen()).benchmark()
    assert len(flops) == len(layer_cfgs)
    assert all(f > 0 for f in flops)
    # repeated blocks profile identically (config-hash dedup)
    assert flops[1] == flops[3] and flops[2] == flops[4]


def _bf16_embeddings(vocab, hidden, max_positions):
    cfg = GptConfig(vocab_size=vocab, hidden_size=hidden, num_hidden_layers=1,
                    num_attention_heads=1, max_position_embeddings=max_positions,
                    dropout_prob=0.0, initializer_range=1.0, dtype="bfloat16")
    module = GptEmbeddings(cfg.to_dict(), deterministic=True)
    params = module.init(jax.random.key(5), np.zeros((1, 4), np.int32))
    return module, params


def _embed_whole_table(module, ids, index):
    # the reference: nn.Embed casts the whole table to bfloat16, then
    # takes the rows
    return module.wte(ids) + module.wpe(decode_positions(index, ids.shape[1]))


@pytest.mark.parametrize("query_len", [1, 7])
@pytest.mark.parametrize("per_row_index", [False, True])
def test_gpt_embeddings_decode_bits_match_whole_table_cast(query_len,
                                                           per_row_index):
    vocab, max_positions = 997, 61
    module, params = _bf16_embeddings(vocab, 48, max_positions)
    rng = np.random.default_rng(query_len)
    ids = rng.integers(0, vocab, (4, query_len)).astype(np.int32)
    ids[0, 0], ids[-1, -1] = 0, vocab - 1
    if per_row_index:
        index = np.array([0, 3, 17, max_positions - query_len], np.int32)
    else:
        index = np.int32(max_positions - query_len)

    new = module.apply(params, ids, index, method=GptEmbeddings.decode)
    old = module.apply(params, ids, index, method=_embed_whole_table)
    assert new.dtype == jnp.bfloat16 and new.shape == (4, query_len, 48)
    np.testing.assert_array_equal(np.asarray(new.view(jnp.uint16)),
                                  np.asarray(old.view(jnp.uint16)))

    # the comparison can tell roundings apart: one cast after a float32
    # add gives other bits
    tables = params["params"]
    f32_sum = (jnp.take(tables["wte"]["embedding"], ids, axis=0)
               + jnp.take(tables["wpe"]["embedding"],
                          decode_positions(index, query_len), axis=0))
    assert np.any(np.asarray(f32_sum.astype(jnp.bfloat16).view(jnp.uint16))
                  != np.asarray(new.view(jnp.uint16)))


@pytest.mark.parametrize("query_len", [1, 7])
def test_gpt_embeddings_decode_casts_no_whole_table(query_len):
    vocab, hidden, max_positions = 997, 48, 61
    module, params = _bf16_embeddings(vocab, hidden, max_positions)
    ids = np.zeros((4, query_len), np.int32)
    index = np.zeros((4,), np.int32)

    def compiled(method):
        fn = jax.jit(lambda p, i, x: module.apply(p, i, x, method=method))
        return fn.lower(params, ids, index).compile().as_text()

    tables = (f"bf16[{vocab},{hidden}]", f"bf16[{max_positions},{hidden}]")
    old = compiled(_embed_whole_table)
    assert all(t in old for t in tables), "the old form no longer shows it"
    new = compiled(GptEmbeddings.decode)
    assert not [t for t in tables if t in new]
