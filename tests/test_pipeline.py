"""Pipeline engine: multi-stage training on the 8-device CPU mesh."""

import jax
import numpy as np
import optax
import pytest

from skycomputing_tpu.dynamics import Allocator, ParameterServer, WorkerManager
from skycomputing_tpu.models import bert_config, bert_layer_configs
from skycomputing_tpu.ops import cross_entropy_loss
from skycomputing_tpu.parallel import PipelineModel


def build_pipeline(devices, n_workers=4, units=2, num_microbatches=1,
                   batch=8, seq=16, slowdowns=None, seed=0, dropout=0.0):
    cfg = bert_config("tiny", dtype="float32", hidden_dropout_prob=dropout,
                      attention_probs_dropout_prob=dropout)
    model_cfg = bert_layer_configs(cfg, num_encoder_units=units,
                                   num_classes=3,
                                   deterministic=(dropout == 0.0))

    wm = WorkerManager()
    wm.load_worker_pool_from_config(
        [
            dict(
                name=f"node-{i}",
                device_config=dict(device_index=i),
                extra_config=dict(
                    slowdown=(slowdowns[i] if slowdowns else 1.0)
                ),
            )
            for i in range(n_workers)
        ]
    )

    class _NoProfile:
        def benchmark(self):
            raise AssertionError("even allocation must not profile")

    Allocator(model_cfg, wm, _NoProfile(), _NoProfile()).even_allocate()

    rng = np.random.default_rng(seed)
    ids = rng.integers(5, 1024, size=(batch, seq)).astype(np.int32)
    types = np.zeros_like(ids)
    mask = np.ones_like(ids)
    labels = rng.integers(0, 3, size=(batch,)).astype(np.int32)

    ps = ParameterServer(model_cfg, example_inputs=(ids, types, mask),
                         rng=jax.random.key(seed))
    model = PipelineModel(
        wm, ps, optax.sgd(1e-2), cross_entropy_loss,
        devices=devices, num_microbatches=num_microbatches,
    )
    return model, (ids, types, mask), labels, ps


def test_stages_live_on_distinct_devices(devices):
    model, *_ = build_pipeline(devices, n_workers=4)
    stage_devices = [s.device for s in model.stages]
    assert len(set(stage_devices)) == 4
    # params actually committed to those devices
    for stage in model.stages:
        leaf = jax.tree_util.tree_leaves(stage.params)[0]
        assert leaf.devices() == {stage.device}


def test_forward_matches_single_device_reference(devices):
    model, data, _, ps = build_pipeline(devices, n_workers=4)
    logits = np.asarray(model.forward(data))
    # reference: the same params applied as one monolithic stack
    ref = np.asarray(ps.stack.apply(ps.params, *data))
    np.testing.assert_allclose(logits, ref, rtol=2e-4, atol=2e-5)


def test_train_step_decreases_loss(devices):
    model, data, labels, _ = build_pipeline(devices, n_workers=4)
    losses = [model.train_step(data, labels, rng=jax.random.key(i))
              for i in range(8)]
    assert losses[-1] < losses[0], losses
    assert model.stats.forward_s > 0
    assert model.stats.backward_s > 0


def test_pipeline_grads_match_monolithic(devices):
    """Per-stage remat backward == one jax.grad over the whole model."""
    model, data, labels, ps = build_pipeline(devices, n_workers=3)

    # monolithic reference grads (before any update)
    def loss_fn(params_list):
        logits = ps.stack.apply(params_list, *data)
        return cross_entropy_loss(logits, labels)

    ref_grads = jax.grad(loss_fn)(ps.params)

    model.train_step(data, labels, rng=jax.random.key(0))
    # recompute pipeline grads by comparing updated params to originals:
    # sgd(lr) => delta = -lr * grad
    lr = 1e-2
    cursor = 0
    for stage in model.stages:
        for li, layer_params in enumerate(stage.get_state_dict()):
            ref = ref_grads[cursor]
            for (path_new, new), (path_ref, g) in zip(
                jax.tree_util.tree_leaves_with_path(layer_params),
                jax.tree_util.tree_leaves_with_path(ref),
            ):
                assert path_new == path_ref
                orig = jax.tree_util.tree_leaves(ps.params[cursor])[
                    [p for p, _ in
                     jax.tree_util.tree_leaves_with_path(ps.params[cursor])
                     ].index(path_new)
                ]
                delta = np.asarray(new) - np.asarray(orig)
                np.testing.assert_allclose(
                    delta, -lr * np.asarray(g), rtol=2e-3, atol=2e-6,
                )
            cursor += 1
    assert cursor == ps.num_layers


def test_microbatched_equals_full_batch_grads(devices):
    """M=4 gradient accumulation must equal the M=1 update (no dropout)."""
    m1, data, labels, _ = build_pipeline(devices, n_workers=3,
                                         num_microbatches=1, seed=3)
    m4, *_ = build_pipeline(devices, n_workers=3, num_microbatches=4, seed=3)
    l1 = m1.train_step(data, labels, rng=jax.random.key(0))
    l4 = m4.train_step(data, labels, rng=jax.random.key(0))
    assert l1 == pytest.approx(l4, rel=1e-5)
    for s1, s4 in zip(m1.stages, m4.stages):
        for a, b in zip(
            jax.tree_util.tree_leaves(s1.params),
            jax.tree_util.tree_leaves(s4.params),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
            )


def test_1f1b_matches_gpipe(devices):
    """1F1B issue order must produce identical training to GPipe."""
    gp, data, labels, _ = build_pipeline(devices, n_workers=4,
                                         num_microbatches=4, seed=7)
    import optax

    from skycomputing_tpu.parallel import PipelineModel

    # rebuild an identical world with the 1f1b schedule
    ob, *_ = build_pipeline(devices, n_workers=4, num_microbatches=4, seed=7)
    ob.schedule = "1f1b"

    l_gp = gp.train_step(data, labels, rng=jax.random.key(0))
    l_ob = ob.train_step(data, labels, rng=jax.random.key(0))
    assert l_gp == pytest.approx(l_ob, rel=1e-5)
    for a, b in zip(gp.stages, ob.stages):
        for x, y in zip(jax.tree_util.tree_leaves(a.params),
                        jax.tree_util.tree_leaves(b.params)):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=1e-5, atol=1e-7)


def test_checkpoint_survives_reallocation(devices, tmp_path):
    """Train 4-way, checkpoint, restore into a 2-way pipeline, same logits."""
    model, data, labels, ps = build_pipeline(devices, n_workers=4)
    model.train_step(data, labels, rng=jax.random.key(0))
    model.sync_to_parameter_server()
    ckpt = str(tmp_path / "ckpt.msgpack")
    ps.save_weights_to_file(ckpt)
    logits_before = np.asarray(model.forward(data))

    # new cluster shape: 2 workers
    model2, _, _, ps2 = build_pipeline(devices, n_workers=2)
    ps2.load_weights_from_file(ckpt)
    model2.load_from_parameter_server()
    logits_after = np.asarray(model2.forward(data))
    np.testing.assert_allclose(logits_before, logits_after, rtol=2e-4,
                               atol=2e-5)


def test_slowdown_inflates_step_time(devices, monkeypatch):
    """Slowdown emulation inflates the step: every slowed program issue
    requests ``elapsed x (factor - 1)`` of extra sleep, a fast stage
    requests none.  Asserted through the injectable clock/sleep hooks so
    the contract is exact under any host load — the wall-clock A/B form
    of this test raced two timed steps and flaked in loaded full-suite
    runs (CHANGES.md PR 11/12)."""
    from skycomputing_tpu.parallel.pipeline import StageRuntime

    fake_t = [0.0]

    def clock():
        fake_t[0] += 0.01  # every read advances one deterministic tick
        return fake_t[0]

    requested = []
    monkeypatch.setattr(StageRuntime, "_clock", staticmethod(clock))
    monkeypatch.setattr(StageRuntime, "_sleep",
                        staticmethod(requested.append))

    fast, data, labels, _ = build_pipeline(devices, n_workers=2, units=1)
    fast.train_step(data, labels, rng=jax.random.key(0))
    assert requested == []  # slowdown 1.0 never sleeps

    slow, *_ = build_pipeline(devices, n_workers=2, units=1,
                              slowdowns=[8.0, 8.0])
    slow.train_step(data, labels, rng=jax.random.key(0))
    # one request per slowed program issue: 2 stages x (fwd + bwd)
    assert len(requested) == 4, requested
    # elapsed reads exactly one 0.01 tick, factor 8 -> 0.07 each
    for sleep_s in requested:
        assert sleep_s == pytest.approx(0.01 * 7.0)


@pytest.mark.slow
def test_default_rng_is_deterministic_across_runs(devices):
    """With dropout live and no caller rng, two identically-built models
    replay the same per-call keys (counter-folded, not wall-clock)."""

    def run():
        model, data, labels, _ = build_pipeline(
            devices, n_workers=2, batch=4, seq=8, dropout=0.1
        )
        return [float(model.train_step(data, labels)) for _ in range(3)]

    assert run() == run()


def test_measure_stage_times_dedups_identical_stages(devices):
    """Stages sharing (structure, input signature, device) reuse one timed
    measurement; distinct structures still measure separately."""
    # 1 + 3*3 + 2 = 12 layers over 4 same-device stages of 3: the two
    # interior stages are identical trio windows (same phase)
    model, data, *_ = build_pipeline(devices[:1] * 4, n_workers=4, units=3)
    times = model.measure_stage_times(data, repeats=1, inner_iters=1)
    assert len(times) == 4
    keys = [s.config_key for s in model.stages]
    for i in range(4):
        for j in range(i + 1, 4):
            if keys[i] == keys[j]:
                assert times[i] == times[j], (i, j, times)
    # at least one pair must have deduped in this partition
    i, j = next(
        (i, j) for i in range(4) for j in range(i + 1, 4)
        if keys[i] == keys[j] and times[i] == times[j]
    )
    # the emulated-degradation factor multiplies the shared raw sample,
    # so a straggler is visible to this pass (self-healing confirms on it)
    model.stages[j].slowdown = 3.0
    slowed = model.measure_stage_times(data, repeats=1, inner_iters=1)
    assert slowed[j] == 3.0 * slowed[i]
