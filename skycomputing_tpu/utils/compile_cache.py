"""Persistent XLA compilation cache wiring.

XLA recompilation is the single largest fixed cost of this framework's
measurement-heavy workflows, and on a fresh accelerator machine nothing
compiled survives from one process to the next.  The in-process jit
cache cannot help across processes — but JAX's persistent compilation
cache can: serialized executables keyed by (HLO, backend, flags, cache
path) survive process exit, so a repeated run pays compile cost once per
*program*, not once per *process*.

``enable_persistent_compilation_cache`` is the single entry point;
``chip_smoke.py``, ``bench.py``, ``experiment/launch.py`` and the
:class:`~..runner.runner.Runner` all go through it.  Where the cache
lives is decided from outside the program or not at all:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX itself reads it into
  ``jax_compilation_cache_dir``; this module sets no directory in code
  and reports that one.
- unset: ``<checkout>/.jax_cache`` (git-ignored) — a fixed path, because
  the path is part of the cache key and a directory that moves (a home
  directory, a temp name, a pid) never hits.
- ``SKYTPU_COMPILE_CACHE=0`` is the off switch;
  ``SKYTPU_COMPILE_CACHE_MIN_S`` is the minimum backend-compile seconds
  for an executable to be persisted (default 0.5 — trivial
  convert/broadcast programs aren't worth the disk round trip).

On the CPU backend the cache stays OFF unless ``JAX_COMPILATION_CACHE_DIR``
asks for it: XLA:CPU executable serialization is not hardened in the
pinned jaxlib — merely enabling the cache under the test suite aborted
the process with glibc heap corruption ("corrupted double-linked list"
inside a donated optimizer update).  TPU serialization is the
production-exercised path and is on by default.
"""

from __future__ import annotations

import os
from typing import Optional

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)

_ACTIVE_DIR: Optional[str] = None


def compilation_cache_dir() -> Optional[str]:
    """The directory the persistent cache is active at, or None."""
    return _ACTIVE_DIR


def enable_persistent_compilation_cache() -> Optional[str]:
    """Turn JAX's persistent compilation cache on at the one place it
    may live (see the module docstring).

    Idempotent (the first successful call wins; later calls return the
    active directory).  Returns the active cache dir, or None when the
    off switch is set or the CPU backend's default-off rule applies.
    An unwritable directory raises: a cache the caller asked for and
    did not get is a cold compile bill on every run, not a detail.
    """
    global _ACTIVE_DIR
    if os.environ.get("SKYTPU_COMPILE_CACHE", "").strip().lower() in (
        "0", "off", "false", "no",
    ):
        return None
    if _ACTIVE_DIR is not None:
        return _ACTIVE_DIR
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        if jax.default_backend() == "cpu":
            # unsafe by default on this backend — see module docstring
            return None
        cache_dir = DEFAULT_CACHE_DIR
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs",
        float(os.environ.get("SKYTPU_COMPILE_CACHE_MIN_S", "0.5")),
    )
    # -1: no size floor — the time floor above is the filter
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _ACTIVE_DIR = cache_dir
    return cache_dir


__all__ = [
    "DEFAULT_CACHE_DIR",
    "compilation_cache_dir",
    "enable_persistent_compilation_cache",
]
