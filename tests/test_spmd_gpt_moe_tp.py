"""MoE x in-pipeline tensor parallelism in the compiled GPT engine.

The last admitted composition hole (the r03 roadmap): expert
tensors join the Megatron col/row role tables — w1/b1 column-shard the
expert intermediate, w2 row-shards it with a psum, router/b2 replicate —
so a tp-sharded MoE pipeline must reproduce the plain MoE pipeline's
logits, aux loss, and a full train step from the same full weights
(the same contract as tests/test_spmd_gpt_tp.py for dense blocks).
"""

import jax
import numpy as np
import pytest

from skycomputing_tpu.parallel import (
    CompiledGptPipeline,
    make_dp_pp_mesh,
    make_dp_pp_tp_mesh,
    make_pipeline_mesh,
)
from skycomputing_tpu.parallel.spmd_gpt import (
    GPT_MOE_TP_COL,
    GPT_MOE_TP_ROW,
)
from skycomputing_tpu.parallel.spmd import (
    merge_stage_params_from_tp,
    split_stage_params_for_tp,
)

from gpt_test_helpers import gpt_data as _data, tiny_gpt_config as _cfg


def test_moe_split_merge_roundtrip(devices):
    cfg = _cfg()
    mesh = make_pipeline_mesh(2, devices)
    pipe = CompiledGptPipeline(cfg, mesh, units_per_stage=2, moe_every=2,
                               num_experts=4)
    ids, _ = _data()
    params = pipe.init(jax.random.key(0), ids)
    stages = jax.tree_util.tree_map(np.asarray, params["stages"])
    split = split_stage_params_for_tp(stages, 2, GPT_MOE_TP_COL,
                                      GPT_MOE_TP_ROW)
    merged = merge_stage_params_from_tp(split, GPT_MOE_TP_COL,
                                        GPT_MOE_TP_ROW)
    jax.tree_util.tree_map(np.testing.assert_array_equal, stages, merged)
    # expert leaves really are sharded (not replicated): w1 [P, tp, E, H,
    # I/tp], w2 [P, tp, E, I/tp, H], router replicated copies
    stage0 = split["unit_1"]["mlp"]
    assert stage0["w1"].shape[-1] * 2 == stages["unit_1"]["mlp"]["w1"].shape[-1]
    assert stage0["w2"].shape[-2] * 2 == stages["unit_1"]["mlp"]["w2"].shape[-2]
    np.testing.assert_array_equal(stage0["router"][:, 0],
                                  stage0["router"][:, 1])


@pytest.mark.parametrize("dp", [1, 2])
@pytest.mark.slow
def test_gpt_moe_tp_pipeline_matches_plain(devices, dp):
    """(dp x) pp x tp MoE == plain pp MoE with the same full weights."""
    cfg = _cfg()
    pp, tp = 2, 2
    ids, labels = _data()

    # the plain baseline carries the same dp axis: MoE routing is
    # per-dp-shard (local capacity), so only tp may differ between the two
    # engines for "tp is pure bookkeeping" to be the contract under test
    plain_mesh = (make_dp_pp_mesh(dp, pp, devices) if dp > 1
                  else make_pipeline_mesh(pp, devices))
    plain = CompiledGptPipeline(
        cfg, plain_mesh, units_per_stage=2,
        num_microbatches=2, moe_every=2, num_experts=4,
    )
    tp_mesh = make_dp_pp_tp_mesh(dp, pp, tp, devices)
    tpd = CompiledGptPipeline(
        cfg, tp_mesh, units_per_stage=2, num_microbatches=2,
        moe_every=2, num_experts=4,
    )

    params = plain.init(jax.random.key(0), ids)
    tpd.init(jax.random.key(0), ids)  # builds tp shardings
    host = lambda t: jax.tree_util.tree_map(np.asarray, t)
    params_tp = jax.device_put(
        dict(
            stages=split_stage_params_for_tp(
                host(params["stages"]), tp, GPT_MOE_TP_COL, GPT_MOE_TP_ROW
            ),
            embeddings=host(params["embeddings"]),
            lm_head=host(params["lm_head"]),
        ),
        tpd.param_shardings,
    )

    logits, aux = plain._logits(params, ids)
    logits_tp, aux_tp = tpd._logits(params_tp, ids)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(logits_tp),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(float(aux), float(aux_tp), rtol=1e-5)

    # one full train step: exercises the expert psum transposition and the
    # replicated-router gradient guard in the backward
    opt = plain.init_opt_state(params)
    opt_tp = tpd.init_opt_state(params_tp)
    params, opt, loss = plain.train_step(params, opt, (ids,), labels)
    params_tp, opt_tp, loss_tp = tpd.train_step(params_tp, opt_tp, (ids,),
                                                labels)
    np.testing.assert_allclose(float(loss), float(loss_tp), rtol=1e-5)

    merged = merge_stage_params_from_tp(
        host(params_tp["stages"]), GPT_MOE_TP_COL, GPT_MOE_TP_ROW
    )
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), b, rtol=2e-4, atol=2e-5
        ),
        host(params["stages"]), merged,
    )


def test_gpt_moe_tp_trains(devices):
    cfg = _cfg()
    pipe = CompiledGptPipeline(
        cfg, make_dp_pp_tp_mesh(1, 2, 2, devices), units_per_stage=2,
        num_microbatches=2, learning_rate=1e-2, moe_every=2, num_experts=4,
    )
    ids, labels = _data()
    params = pipe.init(jax.random.key(0), ids)
    opt = pipe.init_opt_state(params)
    losses = []
    for _ in range(4):
        params, opt, loss = pipe.train_step(params, opt, (ids,), labels)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses
