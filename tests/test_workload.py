"""Workload-plane contracts (CPU-deterministic, tier-1).

The plane's one promise is REPLAYABILITY: a scenario is a value, and
the same seed is byte-for-byte the same workload — across two builds,
two players, two processes, two years.  These tests pin that promise
(trace identity, digest stability, the fractional-rate accumulator),
the named catalog's structural claims (shared prefixes genuinely
shared, skewed tails genuinely heavy), the player's verdict recording
against real engines/fleets, and the bench-compat mixes' byte-identity
with the legacy inline rng loops the committed artifacts were measured
under.
"""

import numpy as np
import pytest

import jax

from skycomputing_tpu.builder import build_layer_stack
from skycomputing_tpu.fleet import AdmissionController, ServingFleet
from skycomputing_tpu.models.gpt import (
    GptConfig,
    generate,
    gpt_layer_configs,
)
from skycomputing_tpu.serving import ServingEngine
from skycomputing_tpu.workload import (
    Dist,
    Phase,
    PrefixPool,
    Scenario,
    ScenarioPlayer,
    build_mix,
    get_scenario,
    scenario_names,
)
from skycomputing_tpu.workload.mixes import (
    fleet_bursty_arrivals,
    fleet_spike_specs,
)

pytestmark = pytest.mark.workload


@pytest.fixture(scope="module")
def gpt():
    cfg = GptConfig(vocab_size=512, hidden_size=64, num_hidden_layers=2,
                    num_attention_heads=2, max_position_embeddings=64,
                    dropout_prob=0.0, dtype="float32")
    layer_cfgs = gpt_layer_configs(cfg, deterministic=True)
    stack = build_layer_stack(layer_cfgs)
    params = stack.init(jax.random.key(7), np.ones((1, 5), np.int32))
    fwd = jax.jit(lambda ids: stack.apply(params, ids))
    return layer_cfgs, params, fwd


def tiny_scenario(seed=3, rate=1.0, ticks=8):
    return Scenario(
        name="tiny", seed=seed,
        phases=(
            Phase(name="only", ticks=ticks, arrival_rate=rate,
                  prompt_len=Dist.uniform(4, 12),
                  new_tokens=Dist.uniform(2, 4),
                  priority_mix=(("interactive", 0.5), ("batch", 0.5))),
        ),
        vocab=(1, 500),
    )


# --------------------------------------------------------------------------
# the stdlib core: validation, determinism, the catalog
# --------------------------------------------------------------------------


def test_dist_and_phase_validation():
    with pytest.raises(ValueError):
        Dist.uniform(5, 2)
    with pytest.raises(ValueError):
        Dist.constant(0)
    with pytest.raises(ValueError):
        Dist.choice((2,), weights=(1.0, 2.0))
    with pytest.raises(ValueError, match="unknown priority"):
        Phase(name="p", ticks=4, arrival_rate=1.0,
              prompt_len=Dist.constant(4), new_tokens=Dist.constant(2),
              priority_mix=(("vip", 1.0),))
    with pytest.raises(ValueError, match="unknown prefix pool"):
        Scenario(name="s", seed=0, phases=(
            Phase(name="p", ticks=4, arrival_rate=1.0,
                  prompt_len=Dist.constant(4),
                  new_tokens=Dist.constant(2),
                  shared_prefix=("nope", 0.5)),
        ))
    with pytest.raises(ValueError, match="vocab"):
        Scenario(name="s", seed=0, vocab=(5, 5), phases=(
            Phase(name="p", ticks=1, arrival_rate=1.0,
                  prompt_len=Dist.constant(4),
                  new_tokens=Dist.constant(2)),
        ))


def test_scenario_trace_determinism_digest_and_accumulator():
    s = tiny_scenario(seed=11, rate=0.5, ticks=10)
    a1 = [a.key() for a in s.arrivals()]
    a2 = [a.key() for a in s.arrivals()]
    assert a1 == a2 and len(a1) == 5
    # fractional rates accumulate deterministically, no rng involved
    assert [a.tick for a in s.arrivals()] == [1, 3, 5, 7, 9]
    assert s.digest() == s.digest()
    assert s.digest() != s.with_seed(12).digest()
    # to_dict carries everything needed to re-declare the scenario
    d = s.to_dict()
    assert d["total_ticks"] == 10 and d["phases"][0]["ticks"] == 10


def test_catalog_contracts():
    assert scenario_names() == [
        "diurnal_ramp", "flash_crowd", "tenant_mix",
        "rag_shared_prefix", "length_skew", "disagg_mix",
    ]
    for name in scenario_names():
        sc = get_scenario(name)
        arrivals = sc.arrivals()
        assert arrivals and all(
            1 <= len(a.prompt) <= sc.max_prompt_len for a in arrivals
        )
    with pytest.raises(ValueError, match="catalog"):
        get_scenario("nope")
    # rag: most arrivals share one of the 4 pool documents
    rag = get_scenario("rag_shared_prefix").arrivals()
    shared = [a for a in rag if a.prefix_pool == "kb_docs"]
    assert len(shared) >= len(rag) // 2
    assert 1 <= len({a.prompt[:a.prefix_len] for a in shared}) <= 4
    # skew: the tail is genuinely heavy
    lens = sorted(len(a.prompt)
                  for a in get_scenario("length_skew").arrivals())
    assert lens[-1] >= 3 * lens[len(lens) // 2]
    # rate/ticks scaling reshapes without re-declaring
    base = get_scenario("flash_crowd")
    double = get_scenario("flash_crowd", rate_scale=2.0,
                          ticks_scale=0.5)
    assert double.total_ticks < base.total_ticks
    assert len(double.arrivals()) > 0


def test_shared_prefix_pool_draws_are_seed_stable():
    s = Scenario(
        name="ragish", seed=5,
        prefix_pools=(
            ("docs", PrefixPool(members=2, length=Dist.constant(6))),
        ),
        phases=(
            Phase(name="p", ticks=12, arrival_rate=1.0,
                  prompt_len=Dist.constant(3),
                  new_tokens=Dist.constant(2),
                  shared_prefix=("docs", 1.0)),
        ),
    )
    arr = s.arrivals()
    assert all(a.prefix_len == 6 and a.prefix_pool == "docs"
               for a in arr)
    assert len({a.prompt[:6] for a in arr}) <= 2
    assert [a.key() for a in s.arrivals()] == [a.key() for a in arr]


# --------------------------------------------------------------------------
# bench-compat mixes: byte-identical to the legacy inline loops
# --------------------------------------------------------------------------


def test_interference_mix_matches_legacy_draw_order():
    icfg = dict(n_churn=4, churn_prompt=(60, 90), churn_new=(4, 8),
                n_small=8, small_prompt=(8, 24), small_new=(10, 16))

    # the pre-workload-plane inline loop, verbatim
    def legacy(rng):
        specs = []
        for _ in range(icfg["n_churn"]):
            plen = int(rng.integers(*icfg["churn_prompt"]))
            n = int(rng.integers(*icfg["churn_new"]))
            specs.append(
                (rng.integers(1, 400, (plen,)).astype(np.int32), n))
        for _ in range(icfg["n_small"]):
            plen = int(rng.integers(*icfg["small_prompt"]))
            n = int(rng.integers(*icfg["small_new"]))
            specs.append(
                (rng.integers(1, 400, (plen,)).astype(np.int32), n))
        order = rng.permutation(len(specs))
        return [specs[i] for i in order]

    for seed in (0, 2):
        old = legacy(np.random.default_rng(seed))
        new = build_mix("interference", np.random.default_rng(seed),
                        icfg=icfg)
        assert len(old) == len(new)
        for (p1, n1), (p2, n2) in zip(old, new):
            assert n1 == n2
            np.testing.assert_array_equal(p1, p2)


def test_fleet_mixes_match_legacy_draw_order():
    # the pre-workload-plane bench_fleet make_request loop, verbatim
    def legacy(rng, n):
        out = []
        for i in range(n):
            plen = int(rng.integers(8, 60))
            prompt = rng.integers(1, 500, (plen,)).astype(np.int32)
            out.append((32 * (i // 8),
                        (prompt, int(rng.integers(16, 28)))))
        return out

    old = legacy(np.random.default_rng(0), 24)
    rng = np.random.default_rng(0)
    new = fleet_bursty_arrivals(rng, n=24, burst=8, gap=32)
    for (t1, (p1, n1)), (t2, (p2, n2)) in zip(old, new):
        assert t1 == t2 and n1 == n2
        np.testing.assert_array_equal(p1, p2)
    # the spike specs continue the SAME stream, like the bench does
    legacy_rng = np.random.default_rng(0)
    legacy(legacy_rng, 24)
    old_spike = legacy(legacy_rng, 4)
    new_spike = fleet_spike_specs(rng, n=4)
    for (_, (p1, n1)), (p2, n2) in zip(old_spike, new_spike):
        assert n1 == n2
        np.testing.assert_array_equal(p1, p2)
    with pytest.raises(ValueError, match="unknown workload mix"):
        build_mix("nope", rng)


# --------------------------------------------------------------------------
# the player against real targets
# --------------------------------------------------------------------------


def test_player_on_engine_verdicts_and_identity(gpt):
    layer_cfgs, params, fwd = gpt
    scenario = tiny_scenario(seed=3, rate=1.0, ticks=8)

    def run_once():
        engine = ServingEngine(layer_cfgs, params, num_slots=2,
                               max_len=64, buckets=(16, 32),
                               prefill_batch=1)
        player = ScenarioPlayer(scenario, engine)
        assert not player.priority_aware  # bare engine, no admission
        return player.play()

    r1, r2 = run_once(), run_once()
    # byte-identical arrival traces across two players (the player
    # never consumes the scenario's rng)
    assert ([v.arrival.key() for v in r1.verdicts]
            == [v.arrival.key() for v in r2.verdicts]
            == [a.key() for a in scenario.arrivals()])
    assert r1.digest == r2.digest == scenario.digest()
    assert len(r1.finished) == len(r1.verdicts)
    for v in r1.finished:
        np.testing.assert_array_equal(
            v.request.output(),
            generate(fwd, v.request.prompt[None],
                     max_new_tokens=v.request.max_new_tokens,
                     context_length=64)[0],
        )
    summary = r1.summary()
    assert summary["total"]["finished"] == len(r1.verdicts)
    assert set(summary["priorities"]) <= {"interactive", "batch"}


def test_player_records_fleet_rejections(gpt):
    layer_cfgs, params, _ = gpt
    fleet = ServingFleet(
        layer_cfgs, params, replicas=1,
        engine_kwargs=dict(num_slots=2, max_len=64, buckets=(16, 32),
                           prefill_batch=1),
        admission=AdmissionController(max_pending=2),
    )
    scenario = tiny_scenario(seed=9, rate=3.0, ticks=4)
    ticks = [0]
    player = ScenarioPlayer(scenario, fleet,
                            sample_fn=lambda: ticks.__setitem__(
                                0, ticks[0] + 1) or {})
    assert player.priority_aware
    report = player.play()
    assert report.rejected, "a 3/tick burst must overrun max_pending=2"
    for v in report.rejected:
        assert v.reason is not None
        assert v.retry_after_s and v.retry_after_s > 0
        assert v.request.status == "rejected"
    assert len(report.finished) == len(report.admitted)
    # the per-tick probe ran once per tick
    assert report.ticks_run == ticks[0] > 0
    # verdict rows serialize for artifacts
    row = report.verdicts[0].to_dict()
    assert {"tick", "phase", "priority", "admitted",
            "status"} <= set(row)
