"""Named numpy request mixes: the benches' legacy rng loops, as values.

The stdlib scenario core (:mod:`.scenario`) owns NEW workloads; this
module owns the two workloads the repo had ALREADY committed bench
artifacts against before the workload plane existed —
a prefill-vs-decode interference mix and ``bench_fleet``'s bursty
steady-state arrivals.  Those artifacts gate
on numbers measured under specific ``numpy.random.Generator`` draw
sequences, so porting them onto ``random.Random`` would silently
change every committed workload.  Instead the EXACT legacy draw
orders live here, once, under stable names: the benches consume them
by name, tests pin byte-identity against the historical sequence, and
no bench carries a private rng loop anymore.

Contract per mix: given the same ``numpy.random.default_rng(seed)``
state and config, the returned specs are byte-identical to what the
pre-workload-plane bench built inline — ``tests/test_workload.py``
replays the legacy loops verbatim and compares.

This module needs numpy (it IS the numpy half of the workload plane);
the stdlib half never imports it.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Tuple

import numpy as np

#: name -> builder; the benches' ``--scenario``-style lookup surface
MIXES: Dict[str, Callable[..., Any]] = {}


def _register(name: str):
    def deco(fn):
        MIXES[name] = fn
        return fn

    return deco


def build_mix(name: str, rng: np.random.Generator, **cfg) -> Any:
    """Resolve a named mix; unknown names fail with the registry in
    the message."""
    builder = MIXES.get(name)
    if builder is None:
        raise ValueError(
            f"unknown workload mix {name!r}; known: {sorted(MIXES)}"
        )
    return builder(rng, **cfg)


@_register("interference")
def interference_specs(
    rng: np.random.Generator, icfg: Dict[str, Any]
) -> List[Tuple[np.ndarray, int]]:
    """The prefill-vs-decode interference mix (the workload chunked
    prefill was built against):
    long-prompt/short-decode CHURNERS whose admission waves are
    expensive, interleaved with short-prompt/short-decode requests
    whose inter-token latency measures the damage.  Shuffled so
    admissions interleave.  Draw order is the committed-artifact
    contract: per churner (plen, n, prompt tokens), then per small
    request the same, then one permutation."""
    specs = []
    for _ in range(icfg["n_churn"]):
        plen = int(rng.integers(*icfg["churn_prompt"]))
        n = int(rng.integers(*icfg["churn_new"]))
        specs.append((rng.integers(1, 400, (plen,)).astype(np.int32), n))
    for _ in range(icfg["n_small"]):
        plen = int(rng.integers(*icfg["small_prompt"]))
        n = int(rng.integers(*icfg["small_new"]))
        specs.append((rng.integers(1, 400, (plen,)).astype(np.int32), n))
    order = rng.permutation(len(specs))
    return [specs[i] for i in order]


def fleet_request_spec(
    rng: np.random.Generator, *, prompt_lo: int = 8,
    prompt_hi: int = 60, vocab: int = 500, new_lo: int = 16,
    new_hi: int = 28,
) -> Tuple[np.ndarray, int]:
    """One ``bench_fleet`` request spec (formerly its inline
    ``make_request``): draw order plen, prompt tokens, max_new —
    byte-compatible with the committed ``BENCH_fleet.json`` workload."""
    plen = int(rng.integers(prompt_lo, prompt_hi))
    prompt = rng.integers(1, vocab, (plen,)).astype(np.int32)
    return prompt, int(rng.integers(new_lo, new_hi))


@_register("fleet_bursty")
def fleet_bursty_arrivals(
    rng: np.random.Generator, *, n: int, burst: int, gap: int,
    start: int = 0, **spec_kw,
) -> List[Tuple[int, Tuple[np.ndarray, int]]]:
    """``bench_fleet``'s steady phase: bursts of ``burst`` requests
    every ``gap`` ticks (the ~90%-utilization knife's-edge shape its
    docstring argues for), each request drawn by
    :func:`fleet_request_spec` in arrival order."""
    return [
        (start + gap * (i // burst), fleet_request_spec(rng, **spec_kw))
        for i in range(int(n))
    ]


@_register("fleet_spike")
def fleet_spike_specs(
    rng: np.random.Generator, *, n: int, **spec_kw,
) -> List[Tuple[np.ndarray, int]]:
    """``bench_fleet``'s admission-spike phase: ``n`` back-to-back
    request specs (the bench paces them 2/tick itself)."""
    return [fleet_request_spec(rng, **spec_kw) for _ in range(int(n))]


__all__ = [
    "MIXES",
    "build_mix",
    "fleet_bursty_arrivals",
    "fleet_request_spec",
    "fleet_spike_specs",
    "interference_specs",
]
