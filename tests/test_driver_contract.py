"""Guards for the entry points as they are run: on virtual CPU devices
for tests, on the chip — one process, no fallback — for measurements."""

import os
import os.path as osp
import subprocess
import sys

import jax
import pytest

REPO = osp.dirname(osp.dirname(osp.abspath(__file__)))


def _clean_env(**extra):
    """The caller's environment without what this suite's conftest set:
    a user's shell, where nothing has chosen a platform or device count."""
    env = dict(os.environ)
    for key in ("JAX_PLATFORMS", "XLA_FLAGS", "SKYTPU_DRYRUN_REEXEC",
                "JAX_COMPILATION_CACHE_DIR"):
        env.pop(key, None)
    env.update(extra)
    return env


@pytest.mark.slow
def test_dryrun_multichip_from_clean_env():
    """dryrun_multichip(n) succeeds from an environment that names no
    platform and no device count: the wrapper starts its own child on n
    virtual CPU devices, whatever the caller's process holds."""
    proc = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__ as g; g.dryrun_multichip(4)"],
        # above the wrapper's own 900s child timeout so a regression
        # surfaces as the wrapper's RuntimeError (with rc + stderr), not
        # a bare TimeoutExpired here
        cwd=REPO, env=_clean_env(), capture_output=True, text=True,
        timeout=1200,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "full feature matrix passed" in proc.stdout
    assert "dryrun[" in proc.stdout  # at least one per-config line


@pytest.fixture(scope="module")
def cpu_children():
    """Two fresh CPU-only interpreters, started together so their
    start-up (a jax import each) overlaps: ``bench.py`` itself, and an
    import of the package followed by a look at jax's backend table."""
    commands = dict(
        bench=[sys.executable, osp.join(REPO, "bench.py")],
        imports=[sys.executable, "-c",
                 "import skycomputing_tpu, skycomputing_tpu.parallel.elastic;"
                 "from jax._src import xla_bridge;"
                 "assert not xla_bridge.backends_are_initialized()"],
    )
    procs = {
        name: subprocess.Popen(
            cmd, cwd=REPO, env=_clean_env(JAX_PLATFORMS="cpu"), text=True,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        )
        for name, cmd in commands.items()
    }
    done = {}
    for name, proc in procs.items():
        out, err = proc.communicate(timeout=300)
        done[name] = (proc.returncode, out, err)
    return done


def test_bench_without_tpu_exits_nonzero_and_prints_no_metric(cpu_children):
    """bench.py measures a TPU: on any other backend it must fail before
    measuring, with nothing on stdout a reader could take for a result
    — no CPU re-exec, no smaller model, no best-so-far line."""
    returncode, out, err = cpu_children["bench"]
    assert returncode != 0
    assert out.strip() == ""
    assert "TPU" in err and "no result" in err


def test_importing_the_package_starts_no_backend(cpu_children):
    """One process per chip: a parent that only IMPORTS the package (the
    ladder runner, the elastic supervisor) must not take the device away
    from the child it starts — backends initialize on first use."""
    returncode, _, err = cpu_children["imports"]
    assert returncode == 0, err[-2000:]


def test_compile_cache_default_is_a_fixed_path_in_the_checkout(monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR unset, an accelerator run caches at
    <checkout>/.jax_cache — never the home directory, a temp name, a pid
    or a time: the path is part of the cache key.  (The variable's own
    case: tests/test_hotpath.py.)"""
    from skycomputing_tpu.utils import compile_cache

    from tests.test_hotpath import _fake_jax

    assert compile_cache.DEFAULT_CACHE_DIR == osp.join(REPO, ".jax_cache")
    monkeypatch.delenv("SKYTPU_COMPILE_CACHE", raising=False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compile_cache, "_ACTIVE_DIR", None)
    recorded = _fake_jax(monkeypatch, "tpu")
    try:
        out = compile_cache.enable_persistent_compilation_cache()
    finally:
        monkeypatch.setattr(compile_cache, "_ACTIVE_DIR", None)
    assert out == compile_cache.DEFAULT_CACHE_DIR
    assert recorded["jax_compilation_cache_dir"] == out
    assert osp.isdir(out)


def test_graft_entry_shapes():
    """entry() must return a traceable fn + example args (shape-level check
    — the driver does the real single-chip compile)."""
    import __graft_entry__ as graft

    fn, args = graft.entry()
    out = jax.eval_shape(fn, *args)
    assert out.shape == (4, 3)


@pytest.mark.parametrize(
    "name,alloc,workers",
    [
        ("even_4.py", "even", 4),
        ("optimal_8.py", "optimal", 8),
        ("dynamic_8_stim.py", "dynamic", 8),
        ("optimal_32_96layer.py", "optimal", 32),
        ("optimal_64_160layer.py", "optimal", 64),
    ],
)
def test_ladder_configs_load(monkeypatch, name, alloc, workers):
    monkeypatch.setenv("SKYTPU_PRESET", "tiny")  # keep model assembly light
    from skycomputing_tpu import load_config

    # ladder configs set SKYTPU_*/STIMULATE in os.environ themselves;
    # snapshot and restore so nothing leaks into later tests
    saved = dict(os.environ)
    try:
        cfg = load_config(
            osp.join(REPO, "experiment", "configs", name)
        )
    finally:
        os.environ.clear()
        os.environ.update(saved)
    assert cfg.allocator_config["type"] == alloc
    assert len(cfg.worker_config) == workers
    assert len(cfg.model_config) > 0
