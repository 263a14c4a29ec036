"""Native solver core: build-on-first-use + ctypes binding.

pybind11 is not in the image, so the C++ core exposes a C ABI and is loaded
with ctypes.  The shared object is compiled from ``solver.cpp`` with g++ on
first use, at a fixed path next to the source.  A library found there is
used only when the stamp beside it proves it is a build of the CURRENT
source: the stamp is one sha256 over the compiler flags, the
``solver.cpp`` it was compiled from and the library itself.  Modification times prove nothing — a
copied or checked-out tree need not keep them — so they are not consulted.
A failed build (no compiler, read-only filesystem) is reported once
through the logger and the pure-Python solver takes over.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import List, Optional, Tuple

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "solver.cpp")
_LIB = os.path.join(_HERE, "libskytpu_solver.so")
_STAMP = _LIB + ".sha256"
_CXX = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17"]

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _stamp_for(lib_path: str) -> str:
    """One digest over what a build is made of and what it made: the
    compiler flags, ``solver.cpp`` and the library's own bytes."""
    digest = hashlib.sha256(" ".join(_CXX).encode())
    for path in (_SRC, lib_path):
        with open(path, "rb") as fh:
            digest.update(fh.read())
    return digest.hexdigest()


def _is_current() -> bool:
    try:
        with open(_STAMP) as fh:
            return fh.read() == _stamp_for(_LIB)
    except OSError:
        return False


def _build() -> None:
    """Make ``_LIB`` a verified build of the current ``solver.cpp``."""
    if _is_current():
        return
    # build to a unique temp name then os.replace: concurrent first-use
    # processes must never dlopen a half-written library
    tmp = f"{_LIB}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            _CXX + [_SRC, "-o", tmp],
            check=True,
            capture_output=True,
            timeout=120,
        )
        stamp = _stamp_for(tmp)
        os.replace(tmp, _LIB)
        with open(f"{_STAMP}.{os.getpid()}.tmp", "w") as fh:
            fh.write(stamp)
        os.replace(fh.name, _STAMP)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def load() -> Optional[ctypes.CDLL]:
    """The solver library, or None when native support is unavailable."""
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        try:
            _build()
            lib = ctypes.CDLL(_LIB)
        except (OSError, subprocess.SubprocessError) as exc:
            from ...utils import Logger

            stderr = getattr(exc, "stderr", None) or b""
            Logger().warning(
                f"native solver unavailable, allocations use the "
                f"pure-Python solver: {exc!r} "
                f"{stderr.decode(errors='replace')[-400:]}".rstrip()
            )
            return None
        lib.skytpu_solve_minmax.restype = ctypes.c_int
        lib.skytpu_solve_minmax.argtypes = [
            ctypes.c_int,
            ctypes.c_int,
            ctypes.POINTER(ctypes.c_double),
            ctypes.POINTER(ctypes.c_double),
            ctypes.POINTER(ctypes.c_double),
            ctypes.POINTER(ctypes.c_double),
            ctypes.c_double,
            ctypes.c_int,
            ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_double),
        ]
        lib.skytpu_solve_large.restype = ctypes.c_int
        lib.skytpu_solve_large.argtypes = [
            ctypes.c_int,
            ctypes.c_int,
            ctypes.POINTER(ctypes.c_double),
            ctypes.POINTER(ctypes.c_double),
            ctypes.POINTER(ctypes.c_double),
            ctypes.POINTER(ctypes.c_double),
            ctypes.c_ulonglong,
            ctypes.c_int,
            ctypes.c_long,
            ctypes.c_double,
            ctypes.c_double,
            ctypes.c_double,
            ctypes.c_double,
            ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_double),
        ]
        lib.skytpu_solve_classes.restype = ctypes.c_int
        lib.skytpu_solve_classes.argtypes = [
            ctypes.c_int,
            ctypes.c_int,
            ctypes.POINTER(ctypes.c_double),
            ctypes.POINTER(ctypes.c_double),
            ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_double),
            ctypes.POINTER(ctypes.c_double),
            ctypes.c_double,
            ctypes.c_int,
            ctypes.c_longlong,
            ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_double),
        ]
        _lib = lib
        return _lib


def solve_minmax_native(
    layer_cost,
    layer_mem,
    device_time,
    device_mem,
    tolerance: float = 1e-3,
    max_iters: int = 60,
) -> Optional[Tuple[List[int], List[Tuple[int, int]], float]]:
    """Native exact solve; None if the library is unavailable or infeasible
    is signalled as a RuntimeError (matching the Python solver)."""
    lib = load()
    if lib is None:
        return None

    L, D = len(layer_cost), len(device_time)
    arr = lambda xs: (ctypes.c_double * len(xs))(*[float(x) for x in xs])
    out_order = (ctypes.c_int * D)()
    out_starts = (ctypes.c_int * D)()
    out_ends = (ctypes.c_int * D)()
    out_bottleneck = ctypes.c_double()

    used = lib.skytpu_solve_minmax(
        L,
        D,
        arr(layer_cost),
        arr(layer_mem),
        arr(device_time),
        arr(device_mem),
        tolerance,
        max_iters,
        out_order,
        out_starts,
        out_ends,
        ctypes.byref(out_bottleneck),
    )
    if used == -2:
        return None  # out-of-range problem size: let Python handle it
    if used < 0:
        raise RuntimeError(
            "allocation infeasible: memory capacities cannot hold the model "
            f"(layers={L}, devices={D})"
        )
    order = [out_order[i] for i in range(used)]
    slices = [(out_starts[i], out_ends[i]) for i in range(used)]
    return order, slices, float(out_bottleneck.value)


def solve_large_native(
    layer_cost,
    layer_mem,
    device_time,
    device_mem,
    seed: int = 0,
    rounds: int = 6,
    evals0: int = 20000,
    wall_cap_s: float = 45.0,
    lower_bound: float = 0.0,
    gap_target: float = 0.01,
    tolerance: float = 1e-3,
) -> Optional[Tuple[List[int], List[Tuple[int, int]], float]]:
    """Native anneal solve for device counts beyond the exact DP's reach.

    Scores a device order by bisecting the minimum bottleneck its greedy
    fixed-order walk achieves, anneals over orders (swap / move /
    bottleneck-targeted swap proposals, eval-count rounds with doubling
    budgets), and hill-climbs slice boundaries on every improvement —
    the same search the pure-Python fallback runs, at a far higher
    evaluation rate.  Deterministic per seed whenever the eval budget
    completes inside ``wall_cap_s`` (under a binding cap an in-round
    check truncates with sub-second overshoot).  None if the library is
    unavailable; RuntimeError when no explored order covers the model.
    """
    lib = load()
    if lib is None:
        return None

    L, D = len(layer_cost), len(device_time)
    arr = lambda xs: (ctypes.c_double * len(xs))(*[float(x) for x in xs])
    out_order = (ctypes.c_int * D)()
    out_starts = (ctypes.c_int * D)()
    out_ends = (ctypes.c_int * D)()
    out_bottleneck = ctypes.c_double()

    used = lib.skytpu_solve_large(
        L,
        D,
        arr(layer_cost),
        arr(layer_mem),
        arr(device_time),
        arr(device_mem),
        int(seed) & 0xFFFFFFFFFFFFFFFF,
        int(rounds),
        int(evals0),
        float(wall_cap_s),
        float(lower_bound),
        float(gap_target),
        float(tolerance),
        out_order,
        out_starts,
        out_ends,
        ctypes.byref(out_bottleneck),
    )
    if used == -2:
        return None
    if used < 0:
        raise RuntimeError(
            "allocation infeasible: memory capacities cannot hold the model "
            f"(layers={L}, devices={D})"
        )
    order = [out_order[i] for i in range(used)]
    slices = [(out_starts[i], out_ends[i]) for i in range(used)]
    return order, slices, float(out_bottleneck.value)


def solve_classes_native(
    layer_cost,
    layer_mem,
    counts,
    class_dt,
    class_mem,
    tolerance: float = 1e-9,
    max_iters: int = 60,
    max_states: int = 8_000_000,
) -> Optional[Tuple[List[int], List[Tuple[int, int]], float]]:
    """Exact count-vector-DP solve over device CLASSES (few distinct
    slowdowns).  Returns (slice classes in pipeline order, slices,
    bottleneck); None when the library is unavailable or the size guard
    trips; RuntimeError when the class instance is infeasible — the
    caller decides whether that dooms the real instance (it does not
    when ``class_mem`` held per-class minima)."""
    lib = load()
    if lib is None:
        return None

    L, K = len(layer_cost), len(class_dt)
    arr = lambda xs: (ctypes.c_double * len(xs))(*[float(x) for x in xs])
    iarr = lambda xs: (ctypes.c_int * len(xs))(*[int(x) for x in xs])
    D = sum(int(c) for c in counts)
    out_class = (ctypes.c_int * D)()
    out_starts = (ctypes.c_int * D)()
    out_ends = (ctypes.c_int * D)()
    out_bottleneck = ctypes.c_double()

    used = lib.skytpu_solve_classes(
        L,
        K,
        arr(layer_cost),
        arr(layer_mem),
        iarr(counts),
        arr(class_dt),
        arr(class_mem),
        float(tolerance),
        int(max_iters),
        int(max_states),
        out_class,
        out_starts,
        out_ends,
        ctypes.byref(out_bottleneck),
    )
    if used == -2:
        return None
    if used < 0:
        raise RuntimeError("class instance infeasible")
    classes = [out_class[i] for i in range(used)]
    slices = [(out_starts[i], out_ends[i]) for i in range(used)]
    return classes, slices, float(out_bottleneck.value)


__all__ = [
    "solve_minmax_native",
    "solve_large_native",
    "solve_classes_native",
    "load",
]
