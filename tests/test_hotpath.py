"""Hot-path regression guards: steady-state recompiles, transfer elision,
dispatch accounting, and the persistent-compile-cache wiring.

These are the CI teeth of the pipeline dispatch overhaul: a change that
reintroduces per-step recompiles, same-device copies, or per-microbatch
zero-cotangent allocation fails here, in tier-1 time, instead of
surfacing as an unexplained bench slowdown three rounds later.
"""

import jax
import numpy as np
import pytest

from skycomputing_tpu.parallel.pipeline import (
    HOTPATH,
    device_put_elided,
    hotpath_counters,
    xla_compile_count,
)
from tests.test_pipeline import build_pipeline

pytestmark = pytest.mark.perf


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_steady_state_never_recompiles(devices, schedule):
    """After step 1, every stage program must be cache-warm: zero XLA
    backend compiles and zero stage-program-cache misses per step."""
    model, data, labels, _ = build_pipeline(
        devices, n_workers=4, units=2, num_microbatches=4
    )
    model.schedule = schedule
    model.train_step(data, labels, rng=jax.random.key(0))  # compile step
    warm = hotpath_counters()
    losses = []
    for i in range(3):
        losses.append(model.train_step(data, labels, rng=jax.random.key(i)))
        assert model.stats.compiles == 0, (
            f"{schedule} step {i + 2} recompiled "
            f"{model.stats.compiles} programs"
        )
    after = hotpath_counters()
    assert after["xla_compiles"] == warm["xla_compiles"]
    assert after["program_cache_misses"] == warm["program_cache_misses"]
    assert all(np.isfinite(l) for l in losses)


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_elision_never_copies_same_device_arrays(devices, schedule):
    """On a single-device pipeline the only real transfers in a steady
    step are the host->device microbatch inputs and labels; every
    inter-stage handoff (activations, cotangents, loss labels) must be
    elided, not copied."""
    if not HOTPATH:
        pytest.skip("legacy dispatch path (SKYTPU_HOTPATH=0)")
    M = 4
    model, data, labels, _ = build_pipeline(
        devices[:1] * 4, n_workers=4, units=2, num_microbatches=M
    )
    model.schedule = schedule
    model.train_step(data, labels, rng=jax.random.key(0))  # warm
    model.train_step(data, labels, rng=jax.random.key(1))
    stats = model.stats
    # host->device copies: one per microbatch per data leaf, plus labels
    n_leaves = len(jax.tree_util.tree_leaves(data))
    assert stats.transfers == M * (n_leaves + 1), (
        f"{schedule}: {stats.transfers} copies — a same-device array "
        f"was copied (expected only the {M * (n_leaves + 1)} "
        f"host->device stagings)"
    )
    assert stats.transfers_elided > 0


def test_dispatch_stats_populated(devices):
    """The dispatch profile ships real numbers: issue time is nonzero,
    bounded by the step wall time, and the phase split adds up."""
    model, data, labels, _ = build_pipeline(
        devices, n_workers=2, units=2, num_microbatches=2
    )
    model.train_step(data, labels, rng=jax.random.key(0))
    stats = model.stats
    wall = stats.forward_s + stats.backward_s + stats.step_s
    assert 0.0 < stats.dispatch_s <= wall + 1e-6
    assert stats.compute_wait_s >= 0.0
    assert stats.dispatch_s + stats.compute_wait_s == pytest.approx(
        wall, rel=1e-6, abs=1e-6
    )


def test_device_put_elided_matches_device_put(devices):
    """Elision is placement-transparent: results land on the target
    device whether or not a copy was needed, and values are unchanged."""
    x_host = np.arange(6, dtype=np.float32).reshape(2, 3)
    tree = {"a": x_host, "b": jax.device_put(x_host * 2, devices[1])}
    out = device_put_elided(tree, devices[1])
    for leaf in jax.tree_util.tree_leaves(out):
        assert leaf.devices() == {devices[1]}
    # same-device leaf is the SAME buffer (identity preserved for donation)
    if HOTPATH:
        assert out["b"] is tree["b"]
    np.testing.assert_array_equal(np.asarray(out["a"]), x_host)


def test_zero_cotangent_tail_cached_across_steps(devices):
    """The GPipe drain builds the zero dy tail once per activation
    structure, not once per microbatch per step."""
    if not HOTPATH:
        pytest.skip("legacy dispatch path (SKYTPU_HOTPATH=0)")
    model, data, labels, _ = build_pipeline(
        devices, n_workers=2, units=2, num_microbatches=4
    )
    model.train_step(data, labels, rng=jax.random.key(0))
    assert len(model._zero_tail_cache) == 1
    cached = next(iter(model._zero_tail_cache.values()))
    model.train_step(data, labels, rng=jax.random.key(1))
    assert next(iter(model._zero_tail_cache.values())) is cached


def test_forced_donation_matches_undonated(devices):
    """SKYTPU_DONATE=1 exercises the donated backward/accumulate programs
    on the CPU backend (where donation is off by default): training must
    be numerically identical to the undonated path, proving the donation
    invariants (inputs dead after backward, totals dead after rebind)."""
    from skycomputing_tpu.parallel import pipeline as pl

    plain, data, labels, _ = build_pipeline(
        devices, n_workers=3, units=2, num_microbatches=4, seed=11
    )
    old = pl._DONATE[0]
    pl._DONATE[0] = True
    try:
        donated, *_ = build_pipeline(
            devices, n_workers=3, units=2, num_microbatches=4, seed=11
        )
        for i in range(2):
            l_p = plain.train_step(data, labels, rng=jax.random.key(i))
            l_d = donated.train_step(data, labels, rng=jax.random.key(i))
            assert l_p == pytest.approx(l_d, rel=1e-6)
        for sp, sd in zip(plain.stages, donated.stages):
            for a, b in zip(jax.tree_util.tree_leaves(sp.params),
                            jax.tree_util.tree_leaves(sd.params)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-6, atol=1e-8)
    finally:
        pl._DONATE[0] = old


def test_compile_count_skips_persistent_cache_hits():
    """jax 0.9 emits the backend-compile duration event around the
    persistent-cache lookup too, so a program the cache served would read
    as a compile (the first chip run counted 270 'compiles' on a cold
    and on a warm cache alike).  The cache's own hit event, emitted just
    before, must cancel it — and only it."""
    from jax import monitoring

    compile_event = "/jax/core/compile/backend_compile_duration"
    base = xla_compile_count()
    monitoring.record_event_duration_secs(compile_event, 0.25)
    assert xla_compile_count() == base + 1
    monitoring.record_event("/jax/compilation_cache/cache_hits")
    monitoring.record_event_duration_secs(compile_event, 0.01)
    assert xla_compile_count() == base + 1  # served, not compiled
    monitoring.record_event_duration_secs(compile_event, 0.25)
    assert xla_compile_count() == base + 2


def test_compilation_cache_opt_out(monkeypatch):
    from skycomputing_tpu.utils import compile_cache

    monkeypatch.setenv("SKYTPU_COMPILE_CACHE", "0")
    assert compile_cache.enable_persistent_compilation_cache() is None


def test_compilation_cache_defaults_off_on_cpu(monkeypatch):
    """No JAX_COMPILATION_CACHE_DIR -> no caching on the CPU backend
    (XLA:CPU executable serialization is not safe in the pinned
    jaxlib)."""
    from skycomputing_tpu.utils import compile_cache

    monkeypatch.delenv("SKYTPU_COMPILE_CACHE", raising=False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert jax.default_backend() == "cpu"
    assert compile_cache.enable_persistent_compilation_cache() is None
    assert compile_cache.compilation_cache_dir() is None


def _fake_jax(monkeypatch, backend):
    """Stand-in ``jax`` recording config updates: the global config is
    process-wide, and CPU serialization is unsafe to actually exercise
    here, so only the placement decision is probed."""
    import sys as _sys

    recorded = {}

    class _FakeConfig:
        @staticmethod
        def update(key, value):
            recorded[key] = value

    class _FakeJax:
        config = _FakeConfig()

        @staticmethod
        def default_backend():
            return backend

    monkeypatch.setitem(_sys.modules, "jax", _FakeJax)
    return recorded


def test_compilation_cache_env_dir_is_the_only_dir(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR is JAX's own knob: the helper reports
    it and sets NO directory in code, on any backend."""
    from skycomputing_tpu.utils import compile_cache

    target = tmp_path / "xla-cache"
    monkeypatch.delenv("SKYTPU_COMPILE_CACHE", raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(target))
    monkeypatch.setattr(compile_cache, "_ACTIVE_DIR", None)
    recorded = _fake_jax(monkeypatch, "cpu")
    try:
        out = compile_cache.enable_persistent_compilation_cache()
    finally:
        monkeypatch.setattr(compile_cache, "_ACTIVE_DIR", None)
    assert out == str(target)
    assert "jax_compilation_cache_dir" not in recorded
    assert not target.exists()  # JAX creates its own directory
