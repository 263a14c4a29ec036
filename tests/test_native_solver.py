"""Native C++ solver core: builds, matches the Python DP and brute force."""

import random

import pytest

from skycomputing_tpu.dynamics.native import load, solve_minmax_native
from skycomputing_tpu.dynamics.solver import solve_contiguous_minmax
from tests.test_solver import brute_force_minmax


needs_native = pytest.mark.skipif(
    load() is None, reason="native solver unavailable (no g++?)"
)


@needs_native
@pytest.mark.parametrize("seed", range(4))
def test_native_matches_brute_force(seed):
    rng = random.Random(seed)
    L = rng.randint(4, 8)
    D = rng.randint(2, 4)
    layer_cost = [rng.uniform(0.5, 3.0) for _ in range(L)]
    layer_mem = [rng.uniform(0.5, 2.0) for _ in range(L)]
    device_time = [rng.uniform(1.0, 4.0) for _ in range(D)]
    device_mem = [sum(layer_mem)] * D

    order, slices, bottleneck = solve_minmax_native(
        layer_cost, layer_mem, device_time, device_mem, tolerance=1e-6
    )
    expected = brute_force_minmax(layer_cost, layer_mem, device_time,
                                  device_mem)
    assert bottleneck == pytest.approx(expected, rel=1e-3)
    # valid partition
    pos = 0
    for s, e in sorted(slices):
        assert s == pos
        pos = e
    assert pos == L


@needs_native
def test_native_matches_python_dp_large():
    rng = random.Random(3)
    L, D = 100, 14  # above the pure-Python exact_limit of 12
    layer_cost = [rng.uniform(0.5, 1.5) for _ in range(L)]
    layer_mem = [rng.uniform(0.1, 0.5) for _ in range(L)]
    device_time = [rng.uniform(1.0, 4.0) for _ in range(D)]
    device_mem = [rng.uniform(5.0, 20.0) for _ in range(D)]

    native = solve_minmax_native(layer_cost, layer_mem, device_time,
                                 device_mem, tolerance=1e-6)
    python = solve_contiguous_minmax(
        layer_cost, layer_mem, device_time, device_mem,
        exact_limit=14, tolerance=1e-6, use_native=False,
    )
    assert native[2] == pytest.approx(python.bottleneck, rel=1e-3)


@needs_native
def test_native_infeasible_raises():
    with pytest.raises(RuntimeError, match="infeasible"):
        solve_minmax_native([1.0, 1.0], [10.0, 10.0], [1.0, 1.0], [1.0, 1.0])


def test_solver_front_door_uses_native_transparently():
    # through the public API the result must be identical either way
    rng = random.Random(9)
    L, D = 30, 6
    layer_cost = [rng.uniform(0.5, 1.5) for _ in range(L)]
    layer_mem = [0.1] * L
    device_time = [rng.uniform(1.0, 4.0) for _ in range(D)]
    device_mem = [100.0] * D
    a = solve_contiguous_minmax(layer_cost, layer_mem, device_time,
                                device_mem, tolerance=1e-6, use_native=True)
    b = solve_contiguous_minmax(layer_cost, layer_mem, device_time,
                                device_mem, tolerance=1e-6, use_native=False)
    assert a.bottleneck == pytest.approx(b.bottleneck, rel=1e-3)


# ---- large-D native anneal (skytpu_solve_large) --------------------------

def _large_instance(W=24, L=60, seed=3):
    rng = random.Random(seed)
    costs = [0.1 + rng.random() for _ in range(L)]
    mem = [1.0] * L
    dt = [1.0 + 2.0 * rng.random() for _ in range(W)]
    dm = [1000.0] * W
    return costs, mem, dt, dm


def test_large_native_covers_and_is_deterministic():
    from skycomputing_tpu.dynamics.native import solve_large_native

    if load() is None:
        pytest.skip("native library unavailable")
    costs, mem, dt, dm = _large_instance()
    # generous wall cap: the eval budget must finish inside it, which is
    # the regime where per-seed determinism is guaranteed
    a = solve_large_native(costs, mem, dt, dm, seed=5, rounds=2,
                           evals0=4000, wall_cap_s=60.0)
    b = solve_large_native(costs, mem, dt, dm, seed=5, rounds=2,
                           evals0=4000, wall_cap_s=60.0)
    assert a is not None and b is not None
    order_a, slices_a, bott_a = a
    order_b, slices_b, bott_b = b
    assert order_a == order_b and slices_a == slices_b and bott_a == bott_b
    # contiguous full coverage
    covered = sorted(slices_a)
    pos = 0
    for s, e in covered:
        assert s == pos and e > s
        pos = e
    assert pos == len(costs)
    # bottleneck is the real max stage load of the returned partition
    worst = max(
        dt[d] * sum(costs[s:e]) for d, (s, e) in zip(order_a, slices_a)
    )
    assert abs(worst - bott_a) < 1e-9


@pytest.mark.slow
def test_large_native_not_worse_than_python_greedy():
    """The whole point of the native anneal: at the same wall budget it
    must match or beat the pure-Python greedy+anneal's bottleneck."""
    if load() is None:
        pytest.skip("native library unavailable")
    costs, mem, dt, dm = _large_instance(W=32, L=80, seed=11)
    nat = solve_contiguous_minmax(costs, mem, dt, dm, anneal_seconds=5)
    py = solve_contiguous_minmax(costs, mem, dt, dm, use_native=False,
                                 anneal_seconds=5)
    # 2% slack: both sides early-exit at gap_target=0.01, so either can
    # stop first depending on wall-clock luck — the claim under test is
    # "native is not meaningfully worse", not bit-equality of optima
    assert nat.bottleneck <= py.bottleneck * 1.02, (
        nat.bottleneck, py.bottleneck
    )


def test_large_native_respects_memory_and_infeasible():
    from skycomputing_tpu.dynamics.native import solve_large_native

    if load() is None:
        pytest.skip("native library unavailable")
    # memory binds: each device holds at most 2 units of mem
    costs = [1.0] * 20
    mem = [1.0] * 20
    dt = [1.0] * 24
    dm = [2.0] * 24
    out = solve_large_native(costs, mem, dt, dm, seed=0, rounds=1,
                             evals0=500, wall_cap_s=10.0)
    assert out is not None
    order, slices, _ = out
    for d, (s, e) in zip(order, slices):
        assert sum(mem[s:e]) <= dm[d] + 1e-9
    # infeasible: total capacity below model footprint
    with pytest.raises(RuntimeError, match="infeasible"):
        solve_large_native(costs, mem, dt, [0.5] * 24, seed=0, rounds=1,
                           evals0=200, wall_cap_s=5.0)


@needs_native
def test_library_is_verified_against_source_hash_not_trusted(
        tmp_path, monkeypatch):
    """A library lying at the fixed path is used only when the stamp
    beside it matches the current source AND the library bytes — a stray,
    stale or altered binary is rebuilt from ``solver.cpp`` (the chip tool
    copies the tree as it stands on disk, ignored files included, and a
    copy keeps no trustworthy mtimes)."""
    import shutil

    from skycomputing_tpu.dynamics import native

    lib = tmp_path / "libskytpu_solver.so"
    src = tmp_path / "solver.cpp"
    shutil.copy(native._SRC, src)
    monkeypatch.setattr(native, "_SRC", str(src))
    monkeypatch.setattr(native, "_LIB", str(lib))
    monkeypatch.setattr(native, "_STAMP", str(lib) + ".sha256")

    lib.write_bytes(b"not a library")  # stray binary, no stamp
    assert not native._is_current()
    native._build()
    assert native._is_current()
    assert lib.read_bytes()[:4] == b"\x7fELF"

    built = lib.read_bytes()
    lib.write_bytes(built + b"\0")  # altered library, stamp untouched
    assert not native._is_current()
    lib.write_bytes(built)
    assert native._is_current()
    src.write_text(src.read_text() + "\n// edited\n")  # newer source
    assert not native._is_current()
    assert [p.name for p in tmp_path.iterdir() if ".tmp" in p.name] == []
