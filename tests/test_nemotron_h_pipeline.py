"""Nemotron-H through the system's own path at tiny widths on the CPU:
the profiler and the allocator on layers that differ, one pipeline step
(a program a stage, and a program a layer) against one ``value_and_grad``,
counters and scopes, and the family's lazy import."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from nemotron_h_helpers import layer_configs, program_loss, close, tiny
from skycomputing_tpu.builder import build_layer_stack
from skycomputing_tpu.dynamics import (
    Allocator,
    DeviceBenchmarker,
    ModelBenchmarker,
    ParameterServer,
    WorkerManager,
)
from skycomputing_tpu.ops import causal_lm_loss
from skycomputing_tpu.parallel import PipelineModel

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- (v) the profiler and the allocator on layers that differ ---------------

def nine_layer_allocation(workers=4):
    cfg = tiny("MEMEM*EME")
    model_cfg = layer_configs(cfg)
    manager = WorkerManager()
    manager.load_worker_pool_from_config([
        dict(name=f"w{i}", device_config=dict(device_index=0),
             extra_config=dict(slowdown=1.0, mem_limit=4096))
        for i in range(workers)
    ])

    class Ids:
        def generate(self):
            return (np.zeros((1, 64), np.int32),)

    class Rows:
        def generate(self):
            return np.ones((8, 32), np.float32)

    model_bench = ModelBenchmarker(model_cfg, Ids())
    device_bench = DeviceBenchmarker(
        manager, Rows(),
        [dict(layer_type="MatmulStack", features=32, depth=2)], iterations=2,
    )
    return model_cfg, manager, model_bench, Allocator(
        model_cfg, manager, model_bench, device_bench)


def test_profiler_keys_each_kind_once(monkeypatch):
    from skycomputing_tpu.dynamics import benchmarker
    from skycomputing_tpu.dynamics.estimator import Estimator

    profiled = []
    real = Estimator.benchmark_model

    def counting(module, *args, **kwargs):
        profiled.append((type(module).__name__,
                         getattr(module, "mixer", None)))
        return real(module, *args, **kwargs)

    monkeypatch.setattr(benchmarker.Estimator, "benchmark_model",
                        staticmethod(counting))
    model_cfg, _, model_bench, _ = nine_layer_allocation()
    costs, mem = model_bench.benchmark()
    assert len(costs) == len(mem) == len(model_cfg) == 11
    assert sorted(profiled, key=str) == sorted([
        ("NemotronHEmbeddings", None), ("NemotronHBlock", "M"),
        ("NemotronHBlock", "E"), ("NemotronHBlock", "*"),
        ("NemotronHHead", None)], key=str)
    kinds = "".join(c.get("mixer", "-") for c in model_cfg)
    by_kind = {}
    for kind, cost in zip(kinds, costs):
        by_kind.setdefault(kind, set()).add(cost)
    assert all(len(v) <= 2 for v in by_kind.values())  # "-": embedding, head
    assert len({min(by_kind[k]) for k in "ME*"}) == 3   # unequal layers


def test_layer_key_is_by_kind_and_config():
    from skycomputing_tpu.dynamics.benchmarker import _layer_key

    aval = (jax.ShapeDtypeStruct((1, 8), jnp.int32),)
    a = dict(layer_type="NemotronHBlock", config=dict(x=1), mixer="M")
    same = dict(mixer="M", config=dict(x=1), layer_type="NemotronHBlock")
    other_kind = dict(a, mixer="E")
    other_type = dict(a, layer_type="NemotronHHead")
    assert _layer_key(a, aval) == _layer_key(same, aval)
    assert len({_layer_key(c, aval) for c in (a, other_kind, other_type)}) == 3


def stage_sizes(manager):
    return [len(w.model_config) for w in sorted(
        manager.worker_pool, key=lambda w: w.rank) if w.model_config]


def test_optimal_differs_from_even_and_is_no_worse():
    _, manager, model_bench, allocator = nine_layer_allocation()
    costs, _ = model_bench.benchmark()

    def bottleneck(sizes):
        cuts = np.cumsum([0] + sizes)
        return max(sum(costs[a:b]) for a, b in zip(cuts, cuts[1:]))

    allocator.even_allocate()
    even = stage_sizes(manager)
    allocator.optimal_allocate()
    optimal = stage_sizes(manager)
    assert sum(even) == sum(optimal) == 11
    assert optimal != even
    assert bottleneck(optimal) <= bottleneck(even)


# -- (vi) one pipeline step equals one value_and_grad -----------------------

def two_stage_model(devices, monkeypatch, layer_programs,
                    optimizer=None, pattern="ME*"):
    """``(model, model_cfg, server, ids)``: the tiny stack over two stages,
    two microbatches; ``layer_programs`` puts the engine's line between a
    program a stage and a program a layer under or over this model."""
    from skycomputing_tpu.parallel import pipeline

    monkeypatch.setattr(pipeline, "LAYER_PROGRAM_MIN_BYTES",
                        0 if layer_programs else 1 << 40)
    cfg = tiny(pattern)
    model_cfg = layer_configs(cfg)
    ids = np.asarray(jax.random.randint(jax.random.key(7), (4, 32), 0, 256))
    server = ParameterServer(model_cfg, example_inputs=(ids,),
                             rng=jax.random.key(0))
    manager = WorkerManager()
    manager.load_worker_pool_from_config([
        dict(name=f"w{i}", device_config=dict(device_index=i),
             extra_config=dict(slowdown=1.0, mem_limit=-1))
        for i in range(2)
    ])
    for worker, span in zip(manager.worker_pool,
                            [(0, 3), (3, len(model_cfg))]):
        worker.model_config = model_cfg[span[0]:span[1]]
        worker.order = worker.rank
    model = PipelineModel(manager, server, optimizer or optax.sgd(1e-2),
                          causal_lm_loss, devices=devices,
                          num_microbatches=2)
    return model, model_cfg, server, ids


@pytest.mark.parametrize("layer_programs", [False, True])
def test_pipeline_step_equals_whole_stack_gradient(devices, monkeypatch,
                                                   layer_programs):
    model, model_cfg, server, ids = two_stage_model(
        devices, monkeypatch, layer_programs)
    cfg = tiny("ME*")
    grads, losses, _ = model.compute_gradients((ids,), ids,
                                               jax.random.key(1))
    loss = float(sum(jax.device_get(l) for l in losses))

    stack = build_layer_stack(model_cfg)
    params = [jnp.asarray(p) for p in
              jax.tree_util.tree_leaves(server.params)]
    tree = jax.tree_util.tree_structure(server.params)

    def whole(leaves):
        p = jax.tree_util.tree_unflatten(tree, leaves)
        halves = [program_loss(stack, p, ids[:2]),
                  program_loss(stack, p, ids[2:])]
        return sum(halves) / 2

    want_loss, want = jax.jit(jax.value_and_grad(whole))(params)
    assert abs(loss - float(want_loss)) <= 1e-5 * float(want_loss)
    got = jax.tree_util.tree_leaves(jax.device_get(grads))
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert close(jnp.asarray(g), w, 2e-4)

    # the expert layer's counters came out beside the data, on the device
    counters = model.read_counters()
    held = np.asarray(counters["expert_tokens"][0])
    assert held.shape == (cfg["experts_held"],)
    assert counters["tokens_routed_here"] == int(held.sum()) > 0
    assert counters["dropped_tokens"] == 0
    assert model.stats.tokens_routed_here == counters["tokens_routed_here"]
    # a call a microbatch, and the one block its 128 pairs fit
    assert counters["moe_calls"] == counters["moe_blocks"] == 2
    assert model.stats.moe_calls == model.stats.moe_blocks == 2
    # a second pass adds to them: they are totals, never reset by a step
    model.compute_gradients((ids,), ids, jax.random.key(1))
    again = model.read_counters()
    assert again["tokens_routed_here"] == 2 * int(held.sum())
    assert again["moe_calls"] == again["moe_blocks"] == 4

    # a whole step, and the way from a trace's instruction to its scope
    before = jax.device_get(model.stages[0].params)
    model.train_step((ids,), ids, jax.random.key(2))
    after = jax.device_get(model.stages[0].params)
    assert not np.allclose(jax.tree_util.tree_leaves(before)[0],
                           jax.tree_util.tree_leaves(after)[0])
    rows = model.scoped_instructions(["ssd_scan", "moe_experts", "gqa_attn"])
    scopes = {(program, scope) for program, _, _, scope in rows}
    assert ("jit_fwd_counted", "moe_experts") in scopes
    assert {s for p, s in scopes if p.startswith("jit_bwd")} >= {
        "ssd_scan", "moe_experts", "gqa_attn", ""}
    # one program a layer: layers of one kind share their programs
    if layer_programs:
        assert [len(s.layers) for s in model.stages] == [3, 2]


def test_a_pipeline_whose_layers_count_nothing_reads_no_counters(devices):
    from skycomputing_tpu.models import bert_config, bert_layer_configs
    from skycomputing_tpu.ops import cross_entropy_loss

    model_cfg = bert_layer_configs(
        bert_config("tiny", dtype="float32"), num_encoder_units=2,
        num_classes=3, deterministic=True)
    manager = WorkerManager()
    manager.load_worker_pool_from_config([
        dict(name=f"w{i}", device_config=dict(device_index=i),
             extra_config=dict(slowdown=1.0)) for i in range(2)
    ])
    half = len(model_cfg) // 2
    for worker, units in zip(manager.worker_pool,
                             [model_cfg[:half], model_cfg[half:]]):
        worker.model_config = units
        worker.order = worker.rank
    ids = np.full((4, 16), 7, np.int32)
    inputs = (ids, np.zeros_like(ids), np.ones_like(ids))
    server = ParameterServer(model_cfg, example_inputs=inputs,
                             rng=jax.random.key(0))
    model = PipelineModel(manager, server, optax.sgd(1e-2),
                          cross_entropy_loss, devices=devices,
                          num_microbatches=2)
    model.train_step(inputs, np.zeros(4, np.int32), jax.random.key(1))
    assert model.read_counters() == {}
    assert model.stats.moe_calls == model.stats.moe_blocks == 0


# -- the engine's two stage forms, and what they keep ------------------------

def test_programs_a_layer_is_chosen_by_the_mean_layer_size():
    from skycomputing_tpu.parallel.pipeline import programs_a_layer

    layer = lambda mbytes: dict(w=np.broadcast_to(
        np.float32(0), (mbytes << 20) // 4))
    assert not programs_a_layer([layer(18)] * 75)       # BERT-large's units
    assert programs_a_layer([layer(176), layer(155), layer(400), layer(94),
                             layer(176)])               # this family's
    assert not programs_a_layer([])


def test_fold_counters_adds_totals_and_keeps_the_last_of_a_last_name():
    from skycomputing_tpu.parallel.pipeline import fold_counters

    totals = [dict(mixer=dict(moe=jnp.array([1, 2]),
                              last_route=dict(idx=jnp.array([7, 7])))), {}]
    sown = [dict(mixer=dict(moe=jnp.array([10, 20]),
                            last_route=dict(idx=jnp.array([3, 4])))), {}]
    folded = fold_counters(totals, sown)
    assert folded[0]["mixer"]["moe"].tolist() == [11, 22]
    assert folded[0]["mixer"]["last_route"]["idx"].tolist() == [3, 4]


def test_evaluation_forward_and_stage_times_in_both_stage_forms(
        devices, monkeypatch):
    """``PipelineModel.forward`` (no backward follows) and
    ``measure_stage_times`` go through a program a layer as through a
    program a stage; a stage's ``params`` can be assigned (the fault
    injector does)."""
    outputs = {}
    for layer_programs in (False, True):
        model, _, _, ids = two_stage_model(devices, monkeypatch,
                                           layer_programs)
        model.train(False)
        first = np.asarray(model.forward((ids,)))
        again = np.asarray(model.forward((ids,)))
        assert np.array_equal(first, again)
        outputs[layer_programs] = first
        times = model.measure_stage_times((ids,), repeats=1, inner_iters=1)
        assert len(times) == 2 and all(t > 0 for t in times)
        stage = model.stages[1]
        stage.params = jax.tree_util.tree_map(lambda x: x * 0, stage.params)
        assert all(not np.asarray(x).any()
                   for x in jax.tree_util.tree_leaves(stage.params))
    assert np.allclose(outputs[False], outputs[True], rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("layer_programs", [False, True])
def test_first_update_is_adamw_on_the_whole_stack_gradient(
        devices, monkeypatch, layer_programs):
    """One ``train_step``: every parameter leaf moves as ``optax.adamw``
    moves it on the gradient of the whole stack, and both moments are
    the first step's; a layer the update skipped would read 1."""
    optimizer = optax.adamw(1e-3)
    model, model_cfg, server, ids = two_stage_model(
        devices, monkeypatch, layer_programs, optimizer=optimizer)
    stack = build_layer_stack(model_cfg)
    before = jax.tree_util.tree_map(jnp.asarray, server.params)

    def whole(p):
        return (program_loss(stack, p, ids[:2])
                + program_loss(stack, p, ids[2:])) / 2

    grads = jax.jit(jax.grad(whole))(before)
    changes, state = optimizer.update(grads, optimizer.init(before), before)
    model.train_step((ids,), ids, jax.random.key(1))
    after = [p for s in model.stages for p in jax.device_get(s.params)]

    def apart(got, want):
        return float(jnp.linalg.norm((got - want).ravel())
                     / max(float(jnp.linalg.norm(want.ravel())), 1e-30))

    moved = jax.tree_util.tree_map(lambda a, b: a - b, after, before)
    errs = jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(apart, moved, changes))
    assert len(errs) == len(jax.tree_util.tree_leaves(before))
    assert max(errs) < 2e-2, errs
    # the optimizer's state, a stage (or a layer) at a time
    states = [s.opt_state for s in model.stages]
    if layer_programs:
        states = [layer for s in states for layer in s]
    mu = jax.tree_util.tree_leaves([st[0].mu for st in states])
    nu = jax.tree_util.tree_leaves([st[0].nu for st in states])
    for got, want in zip(mu, jax.tree_util.tree_leaves(state[0].mu)):
        assert close(jnp.asarray(got), want, 2e-3)
    for got, want in zip(nu, jax.tree_util.tree_leaves(state[0].nu)):
        assert close(jnp.asarray(got), want, 4e-3)
    assert all(int(st[0].count) == 1 for st in states)


def sown_readings(model, config):
    from benchmarks.drivers.train_lm_pipeline import _check_mechanisms
    from benchmarks.reference import nemotron_h as reference

    params = [p for s in model.stages for p in s.params]
    return _check_mechanisms(reference, config, params, model.last_sown())


@pytest.mark.parametrize("layer_programs", [False, True])
def test_what_the_stage_programs_sow_holds_to_the_reference(
        devices, monkeypatch, layer_programs):
    """Every ``M`` layer's scan state and every ``E`` layer's choices, as
    the stage programs of a pass computed them, against the reference over
    the inputs sown beside them; a bfloat16 scan state shows."""
    from skycomputing_tpu.ops import ssd

    pattern = "MEME*"
    model, _, _, ids = two_stage_model(devices, monkeypatch, layer_programs,
                                       pattern=pattern)
    model.compute_gradients((ids,), ids, jax.random.key(1))
    sown = model.last_sown()
    assert ["last_scan" in s for s in sown] == [
        False, True, False, True, False, False, False]
    assert ["last_route" in s for s in sown] == [
        False, False, True, False, True, False, False]
    # the pass's LAST microbatch: the router saw its rows, not the first's
    assert sown[2]["last_route"]["tokens"].shape == (2 * 32, 64)
    sound = sown_readings(model, tiny(pattern))
    assert len(sound["scan_state_rel_l2"]) == 2
    assert len(sound["router_choices_apart"]) == 2
    assert max(sound["scan_state_rel_l2"]) < 1e-5
    assert max(sound["router_choices_apart"]) == 0.0
    # totals still add up beside the last-call values
    assert model.read_counters()["dropped_tokens"] == 0

    # the same stack with the chunk states in bfloat16 (another norm_eps:
    # programs are cached by config)
    monkeypatch.setattr(ssd, "STATE_DTYPE", jnp.bfloat16)
    cfg = tiny(pattern, norm_eps=2e-5)
    model_cfg = layer_configs(cfg)
    server = ParameterServer(model_cfg, example_inputs=(ids,),
                             rng=jax.random.key(0))
    manager = WorkerManager()
    manager.load_worker_pool_from_config([
        dict(name="w0", device_config=dict(device_index=0),
             extra_config=dict(slowdown=1.0, mem_limit=-1))])
    manager.worker_pool[0].model_config = model_cfg
    manager.worker_pool[0].order = 0
    lowered = PipelineModel(manager, server, optax.sgd(1e-2), causal_lm_loss,
                            devices=devices, num_microbatches=2)
    lowered.compute_gradients((ids,), ids, jax.random.key(1))
    assert min(sown_readings(lowered, cfg)["scan_state_rel_l2"]) > 2e-4


# -- (viii) the family is imported when a config names it, not before -------

def test_package_imports_do_not_import_the_family():
    code = (
        "import sys\n"
        "import skycomputing_tpu, skycomputing_tpu.serving\n"
        "import experiment.launch\n"
        "assert 'skycomputing_tpu.models.nemotron_h' not in sys.modules\n"
        "assert 'skycomputing_tpu.ops.ssd' not in sys.modules\n"
        "assert 'skycomputing_tpu.ops.moe_dropless' not in sys.modules\n"
        "from skycomputing_tpu.registry import LAYER\n"
        "LAYER.get_module('NemotronHBlock')\n"
        "assert 'skycomputing_tpu.models.nemotron_h' in sys.modules\n"
    )
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
