"""The weights a serving engine holds (``serving/weights.py``,
``models/gpt.ROUNDED_PARAMS``) and the
copy-on-write page copy that consumes its slab (tier-1, CPU).

A GPT that computes in bfloat16 from float32 parameters rounds every
dense kernel and bias to bfloat16 inside each program; the engine holds
those leaves rounded, and nothing else.  The contract is bit identity:
every program the engine compiles gives the same bits from the held tree
as from the float32 tree it was built from.
"""

import json
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skycomputing_tpu.builder import build_layer_stack
from skycomputing_tpu.models import gpt as gpt_mod
from skycomputing_tpu.models.gpt import (
    GptBlock_Attn,
    GptConfig,
    apply_kv_paged,
    decode_modules,
    draft_slice_indices,
    gpt_layer_configs,
    served_param_dtypes,
)
from skycomputing_tpu.serving import QuantizedPages, Request, ServingEngine
from skycomputing_tpu.serving import engine as engine_mod
from skycomputing_tpu.serving.kv_cache import (
    init_paged_caches,
    kv_spec_from_config,
)
from skycomputing_tpu.serving.speculative import tree_param_mb
from skycomputing_tpu.serving.weights import held_avals, hold, weight_bytes

pytestmark = pytest.mark.serving

BF16 = np.dtype(jnp.bfloat16)
F32 = np.dtype(np.float32)
CELL_CONFIG = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "benchmarks", "configs", "gpt2-large.json",
)


def _cell_config(rehearse: bool) -> GptConfig:
    with open(CELL_CONFIG) as f:
        c = json.load(f)
    if rehearse:
        c.update({k: v for k, v in c["rehearse"].items() if k != "engine"})
    return GptConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        num_hidden_layers=c["num_hidden_layers"],
        num_attention_heads=c["num_attention_heads"],
        intermediate_size=c["intermediate_size"],
        max_position_embeddings=c["max_position_embeddings"],
        hidden_act=c["hidden_act"], dtype=c["compute_dtype"],
    )


@pytest.fixture(scope="module")
def bf16_gpt():
    """The serving cell's model at its rehearse sizes: bfloat16 compute,
    float32 parameters.  Every leaf is moved off its initial value (a
    LayerNorm's ones and zeros round to bfloat16 exactly), so a leaf
    held narrower than its programs use it changes their bits."""
    cfg = _cell_config(rehearse=True)
    layer_cfgs = gpt_layer_configs(cfg, deterministic=True)
    stack = build_layer_stack(layer_cfgs)
    params = stack.init(jax.random.key(11), np.ones((1, 5), np.int32))
    rng = np.random.default_rng(11)
    params = jax.tree_util.tree_map(
        lambda x: x + np.float32(0.05) * rng.standard_normal(
            x.shape, np.float32), params)
    return cfg, layer_cfgs, params


def _engine(layer_cfgs, params, devices, **kw):
    base = dict(num_slots=3, max_len=48, buckets=(8, 16), page_size=8,
                partition=[3, 3], devices=devices[:2], prefill_batch=2)
    base.update(kw)
    return ServingEngine(layer_cfgs, params, **base)


def _requests(seed):
    rng = np.random.default_rng(seed)
    return [
        Request(prompt=rng.integers(1, 512, (n,)).astype(np.int32),
                max_new_tokens=m)
        for n, m in [(5, 7), (12, 5), (3, 9), (14, 6)]
    ]


def _same_bits(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape
        assert x.tobytes() == y.tobytes()


class _Recorder:
    """Wraps a program: every call's arguments and result, in order."""

    def __init__(self, name, fn, calls):
        self.name, self.fn, self.calls = name, fn, calls

    def __call__(self, *args):
        out = self.fn(*args)
        self.calls.append((self.name, self.fn, args, out))
        return out


def _record(engine, monkeypatch):
    calls = []
    for k, st in enumerate(engine.stages):
        # a new dict: the process-level program cache keeps its handles
        st.programs = {
            kind: _Recorder(("stage", k, kind), fn, calls)
            for kind, fn in st.programs.items()
        }
    for name in ("prefill_first_token", "decode_next",
                 "spec_verify_argmax"):
        monkeypatch.setattr(engine_mod, name,
                            _Recorder(name, getattr(engine_mod, name),
                                      calls))
    if engine._draft is not None:
        d = engine._draft
        d._loop_donated = _Recorder("spec_draft", d._loop_donated, calls)
    return calls


@pytest.mark.parametrize("spec_k", [0, 2], ids=["plain", "speculative"])
def test_every_program_gives_the_same_bits_from_the_held_weights(
        bf16_gpt, devices, monkeypatch, spec_k):
    """Every program the engine runs in a greedy run — a prefill wave
    at each bucket on each stage, the first-token program, the decode
    tick and its next-token program; with speculation the verify
    forward, its argmax and the draft loop — is run again on the
    float32 tree it was built from: the logits (or tokens), and every
    page slab it returns, are the same bits."""
    _, layer_cfgs, params = bf16_gpt
    engine = _engine(layer_cfgs, params, devices, spec_k=spec_k,
                     draft_blocks=1 if spec_k else None)
    assert engine.stats.weight_bytes_narrowed > 0
    calls = _record(engine, monkeypatch)
    engine.run(_requests(3))

    f32_stage, cursor = [], 0
    for st in engine.stages:
        n = len(st.modules)
        f32_stage.append([jax.device_put(p, st.device)
                          for p in params[cursor:cursor + n]])
        cursor += n
    seen, logits_f32 = set(), {}
    for name, fn, args, out in calls:
        if name[0] == "stage":
            _, k, kind = name
            assert engine.stages[k].params is args[0]
            again = fn(f32_stage[k], *args[1:])
            logits_f32[id(out[0])] = again[0]
            seen.add((k, kind, args[1].shape[1]))
        elif name == "spec_draft":
            modules = [m for st in engine.stages for m in st.modules]
            idx = draft_slice_indices(modules, engine.draft_blocks)
            dev = engine.stages[0].device
            draft_f32 = [jax.device_put(params[i], dev) for i in idx]
            again = fn(draft_f32, *args[1:])
            seen.add(name)
        else:
            # a program of the logits: fed the float32 tree's logits
            again = fn(logits_f32[id(args[0])], *args[1:])
            seen.add(name)
        _same_bits(out, again)

    if spec_k:
        assert {"spec_verify_argmax", "spec_draft"} <= seen
        assert {(k, "spec_verify", spec_k + 1) for k in (0, 1)} <= seen
    else:
        assert {"prefill_first_token", "decode_next"} <= seen
        assert {(k, "decode_tick", 1) for k in (0, 1)} <= seen
        assert {(k, "prefill_wave", b) for k in (0, 1)
                for b in (8, 16)} <= seen


def test_greedy_streams_are_those_of_the_float32_tree(
        bf16_gpt, devices, monkeypatch):
    """An engine that holds the narrowed tree emits the same greedy
    streams as one made to hold every leaf as given."""
    _, layer_cfgs, params = bf16_gpt
    held = _engine(layer_cfgs, params, devices)
    held_reqs = _requests(5)
    held.run(held_reqs)

    def as_given(module, params):
        return jax.tree_util.tree_map(lambda x: np.dtype(x.dtype), params)

    monkeypatch.setattr(engine_mod, "served_param_dtypes", as_given)
    given = _engine(layer_cfgs, params, devices)
    assert given.stats.weight_bytes_narrowed == 0
    assert held.stats.weight_bytes_narrowed > 0
    given_reqs = _requests(5)
    given.run(given_reqs)
    for a, b in zip(held_reqs, given_reqs):
        assert a.status == b.status == "finished"
        np.testing.assert_array_equal(a.output(), b.output())


def _leaf_dtypes(engine):
    """``{(layer, path): dtype}`` over every stage's held params."""
    out, layer = {}, 0
    for st in engine.stages:
        for p in st.params:
            for path, leaf in jax.tree_util.tree_flatten_with_path(p)[0]:
                out[(layer, jax.tree_util.keystr(path))] = leaf.dtype
            layer += 1
    return out


def test_layernorms_and_tables_stay_float32_and_dense_leaves_narrow(
        bf16_gpt, devices):
    """LayerNorm scales and biases are used in float32 and the token and
    position tables are gathered before their cast: all stay float32.
    Every dense kernel and bias is rounded where it is used: bfloat16.
    At the rehearse sizes that is the whole count of narrowed bytes."""
    cfg, layer_cfgs, params = bf16_gpt
    engine = _engine(layer_cfgs, params, devices)
    dtypes = _leaf_dtypes(engine)
    for (layer, path), dtype in dtypes.items():
        if "ln_" in path or "wte" in path or "wpe" in path:
            assert dtype == F32, (layer, path)
        else:
            assert ("kernel" in path or "bias" in path) and dtype == BF16, (
                layer, path, dtype)
    H, I, V = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    dense = (cfg.num_hidden_layers * (4 * (H * H + H) + 2 * H * I + I + H)
             + H * V + V)
    assert engine.stats.weight_bytes_narrowed == 2 * dense
    kept = V * H + cfg.max_position_embeddings * H + (
        2 * cfg.num_hidden_layers + 1) * 2 * H
    assert engine.stats.weight_bytes_served == 2 * dense + 4 * kept
    snap = engine.stats.snapshot()
    assert snap["weight_bytes_narrowed"] == 2 * dense
    assert engine_mod.ServingStats.FIELD_TYPES[
        "weight_bytes_served"] == "gauge"


def test_the_cell_holds_most_of_its_weight_bytes_narrowed():
    """At the serving cell's full size (abstractly: nothing is
    allocated) the programs read 1.81 GB of weights, of which 1.55 GB
    are held in bfloat16 — 85% of the bytes; the token table, the
    position table and the LayerNorms make the rest."""
    cfg = _cell_config(rehearse=False)
    layer_cfgs = gpt_layer_configs(cfg, deterministic=True)
    stack = build_layer_stack(layer_cfgs)
    avals = jax.eval_shape(
        lambda: stack.init(jax.random.key(0), np.ones((1, 8), np.int32))
    )
    held = [held_avals(p, served_param_dtypes(m, p))
            for m, p in zip(decode_modules(stack), avals)]
    served, narrowed = weight_bytes(avals, held)
    assert narrowed == 1_545_165_474
    assert served == 1_808_471_714
    assert narrowed / served >= 0.85


# operations that move a value without changing it: a conversion
# commutes with them, so a leaf reshaped and then converted is rounded
_MOVES = ("reshape", "broadcast_in_dim", "squeeze", "expand_dims",
          "transpose")


def _rounded_to(jaxpr, var):
    """The dtypes ``var`` reaches a ``convert_element_type`` at, through
    moves and nested calls; ``None`` where any use reads it otherwise
    (at its width, or returned)."""
    if any(v is var for v in jaxpr.outvars):
        return None
    seen = set()
    for eqn in jaxpr.eqns:
        at = [i for i, v in enumerate(eqn.invars) if v is var]
        if not at:
            continue
        name = eqn.primitive.name
        if name == "convert_element_type":
            seen.add(np.dtype(eqn.params["new_dtype"]))
            continue
        inner = eqn.params.get("jaxpr", eqn.params.get("call_jaxpr"))
        if name in _MOVES:
            found = _rounded_to(jaxpr, eqn.outvars[0])
        elif inner is not None and len(inner.jaxpr.invars) == len(
                eqn.invars):
            found = set()
            for i in at:
                got = _rounded_to(inner.jaxpr, inner.jaxpr.invars[i])
                found = None if got is None or found is None else (
                    found | got)
        else:
            return None
        if found is None:
            return None
        seen |= found
    return seen


def _undeclared_and_misdeclared(modules, params, positions):
    """Traced through the serving forward at ``positions`` a row: the
    leaves every use rounds to one narrower dtype that the declaration
    holds as given, and the leaves it holds narrower that some use reads
    otherwise."""
    page = 4
    specs = [kv_spec_from_config(gpt_mod._gcfg(m.config).to_dict(), page)
             for m in modules if isinstance(m, GptBlock_Attn)]
    slabs = jax.eval_shape(lambda: init_paged_caches(specs, 4, page))
    rows = 2
    ints = jax.ShapeDtypeStruct((rows,), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda p, x, s, t, i, v: apply_kv_paged(modules, p, x, s, t, i, v)
    )(params, jax.ShapeDtypeStruct((rows, positions), jnp.int32), slabs,
      jax.ShapeDtypeStruct((rows, 2), jnp.int32), ints, ints).jaxpr
    leaves = jax.tree_util.tree_leaves_with_path(params)
    held = jax.tree_util.tree_leaves(
        [served_param_dtypes(m, p) for m, p in zip(modules, params)])
    undeclared, misdeclared = [], []
    for (path, leaf), var, dtype in zip(leaves, jaxpr.invars, held):
        rounded = _rounded_to(jaxpr, var)
        name = jax.tree_util.keystr(path)
        if dtype != leaf.dtype:
            if rounded != {dtype}:
                misdeclared.append(name)
        elif (rounded is not None and len(rounded) == 1
              and next(iter(rounded)).itemsize < leaf.dtype.itemsize):
            undeclared.append(name)
    return undeclared, misdeclared


def _stack_avals(dtype):
    """Every layer kind the engine serves, the experts' layer too, at a
    tiny size: modules and abstract params."""
    cfg = GptConfig(vocab_size=97, hidden_size=32, num_hidden_layers=2,
                    num_attention_heads=4, max_position_embeddings=64,
                    dtype=dtype)
    layer_cfgs = gpt_layer_configs(cfg, deterministic=True, moe_every=2,
                                   num_experts=4)
    stack = build_layer_stack(layer_cfgs)
    avals = jax.eval_shape(
        lambda: stack.init(jax.random.key(0), np.ones((1, 8), np.int32)))
    return decode_modules(stack), avals


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("positions", [1, 7], ids=["tick", "wave"])
def test_the_declaration_is_what_the_serving_forward_rounds(
        dtype, positions):
    """``ROUNDED_PARAMS`` against the forward every stage program runs,
    at a decode tick's and a wave's shape: each leaf held narrower is
    only ever converted to that dtype (through moves and nested calls),
    and no leaf that every use rounds is left at its width.  A model
    that computes in float32 holds nothing narrower."""
    modules, avals = _stack_avals(dtype)
    kinds = {type(m).__name__ for m in modules}
    assert {"GptBlock_Mlp", "GptBlock_MoeMlp", "GptLmHead"} <= kinds
    assert _undeclared_and_misdeclared(modules, avals, positions) == (
        [], [])
    narrowed = weight_bytes(avals, [
        held_avals(p, served_param_dtypes(m, p))
        for m, p in zip(modules, avals)])[1]
    assert (narrowed > 0) == (dtype == "bfloat16")


def test_the_check_tells_a_leaf_read_at_its_width(monkeypatch):
    """The check above is able to fail: declared rounded, a LayerNorm
    scale (multiplied in float32) and the token table (gathered) are
    reported; left out of the declaration, a projection is too."""
    monkeypatch.setitem(gpt_mod.ROUNDED_PARAMS, GptBlock_Attn,
                        ("ln_1", "k_proj", "v_proj", "c_proj"))
    monkeypatch.setitem(gpt_mod.ROUNDED_PARAMS, gpt_mod.GptEmbeddings,
                        ("wte",))
    modules, avals = _stack_avals("bfloat16")
    undeclared, misdeclared = _undeclared_and_misdeclared(modules, avals, 1)
    attn = [i for i, m in enumerate(modules)
            if isinstance(m, GptBlock_Attn)]
    assert undeclared == [f"[{i}]['q_proj']['{leaf}']"
                          for i in attn for leaf in ("bias", "kernel")]
    assert misdeclared == ["[0]['wte']['embedding']"] + [
        f"[{i}]['ln_1']['{leaf}']" for i in attn
        for leaf in ("bias", "scale")]


def test_a_layer_kind_the_declaration_does_not_name_keeps_every_leaf():
    """A module of a kind ``ROUNDED_PARAMS`` does not name is held as
    given, Dense leaves included: nothing is narrowed by guess."""

    class Scaled(nn.Module):
        config: dict

        @nn.compact
        def __call__(self, x):
            gain = self.param("gain", nn.initializers.ones, (8,),
                              jnp.float32)
            y = nn.Dense(8, dtype=jnp.bfloat16, param_dtype=jnp.float32,
                         name="c_proj")(x)
            return y * gain

    m = Scaled({"dtype": "bfloat16"})
    p = m.init(jax.random.key(0), jnp.ones((2, 8), jnp.bfloat16))["params"]
    assert served_param_dtypes(m, p) == {
        "gain": F32, "c_proj": {"kernel": F32, "bias": F32}}


def test_narrowing_a_narrowed_tree_changes_nothing(bf16_gpt, devices):
    """The rule over the held tree gives the held dtypes back, ``hold``
    returns the same bits, and an engine built from the held tree holds
    it as given: nothing more is narrowed."""
    _, layer_cfgs, params = bf16_gpt
    first = _engine(layer_cfgs, params, devices)
    held = [p for st in first.stages for p in st.params]
    modules = [m for st in first.stages for m in st.modules]
    for m, p in zip(modules, held):
        d = served_param_dtypes(m, p)
        assert jax.tree_util.tree_map(lambda x: np.dtype(x.dtype), p) == d
        _same_bits(p, hold(p, d, devices[0]))
    second = _engine(layer_cfgs, held, devices)
    assert second.stats.weight_bytes_narrowed == 0
    assert (second.stats.weight_bytes_served
            == first.stats.weight_bytes_served)
    for a, b in zip([p for st in second.stages for p in st.params], held):
        _same_bits(a, b)


def test_the_draft_head_copy_is_charged_at_its_held_bytes(
        bf16_gpt, devices):
    """Speculation over two stages copies the LM head to stage 0: what
    the pre-flight charges for it, before and after the engine exists,
    is the narrowed head the draft holds."""
    _, layer_cfgs, params = bf16_gpt
    engine = _engine(layer_cfgs, params, devices, spec_k=2,
                     draft_blocks=1)
    head = engine._draft.params[-1]
    assert head["lm_head"]["kernel"].dtype == BF16
    estimate = tree_param_mb(held_avals(params[-1], served_param_dtypes(
        engine.stages[-1].modules[-1], params[-1])))
    assert estimate == pytest.approx(tree_param_mb(head))
    assert engine._draft_mb == pytest.approx(estimate)
    assert estimate < 0.6 * tree_param_mb(params[-1])
    later = _engine(layer_cfgs, params, devices, draft_blocks=1)
    assert later._pending_draft_mb() == pytest.approx(estimate)


def _fill(slabs, rng):
    def one(slab):
        if isinstance(slab, QuantizedPages):
            return QuantizedPages(
                jnp.asarray(rng.integers(-127, 128, slab.values.shape),
                            jnp.int8),
                jnp.asarray(rng.random(slab.scale.shape), jnp.float32),
            )
        return jnp.asarray(rng.random(slab.shape), slab.dtype)

    return [(one(k), one(v)) for k, v in slabs]


def _expected_copy(slabs, plan):
    def one(slab):
        if isinstance(slab, QuantizedPages):
            values, scale = np.array(slab.values), np.array(slab.scale)
            for kind, src, dst in plan:
                a = values if kind == "values" else scale
                a[dst] = a[src]
            return QuantizedPages(values, scale)
        out = np.array(slab)
        for _, src, dst in plan:
            out[dst] = out[src]
        return out

    return [(one(k), one(v)) for k, v in slabs]


def test_the_cow_page_copy_aliases_its_slab_where_donation_is_on(
        monkeypatch):
    """With donation on, the program behind ``cow_copy_page`` aliases
    its slab to its result; with it off it does not."""
    slab = jnp.zeros((4, 8, 16), jnp.bfloat16)
    texts = {}
    for donate in (False, True):
        monkeypatch.setattr(engine_mod, "_donation_enabled",
                            lambda d=donate: d)
        program = engine_mod._COW_COPY[engine_mod._donation_enabled()]
        texts[donate] = program.lower(slab, np.int32(1),
                                      np.int32(2)).as_text()
    assert "tf.aliasing_output = 0" in texts[True]
    assert "tf.aliasing_output" not in texts[False]


@pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["fp", "int8"])
@pytest.mark.parametrize("donate", [False, True],
                         ids=["undonated", "donated"])
def test_apply_cow_plan_copies_the_same_pages_donated_or_not(
        bf16_gpt, devices, monkeypatch, kv_dtype, donate):
    """``apply_cow_plan`` gives the planned slabs on fp and int8 pools
    (values and their scales) whether the page copy donates or not; a
    donated slab's old handle is gone, rebound to its copy."""
    _, layer_cfgs, params = bf16_gpt
    engine = _engine(layer_cfgs, params, devices, kv_dtype=kv_dtype,
                     partition=None)
    st = engine.stages[0]
    st.slabs = _fill(st.slabs, np.random.default_rng(4))
    plan = [("values", 1, 3)]
    if kv_dtype == "int8":
        plan.append(("scales", 1, 3))
    want = _expected_copy(st.slabs, plan)
    before = list(st.slabs)
    monkeypatch.setattr(engine_mod, "_donation_enabled", lambda: donate)
    st.apply_cow_plan(plan)
    _same_bits(st.slabs, want)
    for old, new in zip(jax.tree_util.tree_leaves(before),
                        jax.tree_util.tree_leaves(st.slabs)):
        assert old is not new
        assert old.is_deleted() == donate
