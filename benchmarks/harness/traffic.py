"""The one general traffic generator.  A mix is a data file of
parameters (``benchmarks/traffic/<mix>.json``); this turns it, with a
seed, into requests with lengths, token ids and due times.

Steadiness: every seed is given the SAME work.  The pool of ``pool``
(prompt length, new tokens) pairs is the mix's two distributions read at
evenly spaced quantiles; how they are paired, the order in which each
pass through the pool offers them, and the gaps between arrivals all
come from ``shape_seed``, which belongs to the mix and not to the run.
The run's ``--seed`` draws the token ids (and, in the driver, the
weights).  A window takes in a few tens of requests: where the seed also
chose their order, the prompt tokens prefilled in a 20 s window differed
by a quarter between seeds and the tokens per second by 2%, against
0.03% between two runs of one seed (my chip runs, PR 24).

    "lengths": {"prompt":     {"dist": "lognormal", "median": 192,
                               "sigma": 0.8, "min": 16, "max": 768},
                "new_tokens": {"dist": "fixed", "value": 64},
                "pool": 32, "shape_seed": 0}
    "arrivals": {"kind": "backlog", "min_waiting": 64}
              | {"kind": "poisson", "rate_per_s": 1.5}
              | {"kind": "gamma", "rate_per_s": 1.5, "cv": 3.0}
    "sharing":  {"prefix_tokens": 512, "groups": 4}      (optional)
"""

from __future__ import annotations

import statistics
from dataclasses import dataclass
from typing import Iterator, List, Optional

import numpy as np


@dataclass
class Spec:
    """One request as generated: the program gets only ``prompt`` and
    ``new_tokens``; ``due_s`` (seconds from the window's opening; None
    under a backlog) stays with the driver."""

    prompt: np.ndarray
    new_tokens: int
    due_s: Optional[float]


def quantile_lengths(dist: dict, n: int) -> List[int]:
    """``n`` lengths: the distribution at quantiles (i + 0.5) / n."""
    kind = dist["dist"]
    if kind == "fixed":
        return [int(dist["value"])] * n
    if kind == "lognormal":
        normal = statistics.NormalDist()
        out = []
        for i in range(n):
            z = normal.inv_cdf((i + 0.5) / n)
            x = dist["median"] * float(np.exp(dist["sigma"] * z))
            out.append(int(min(max(round(x), dist["min"]), dist["max"])))
        return out
    raise ValueError(f"unknown length distribution {kind!r}")


def length_pool(lengths: dict) -> List[tuple]:
    n = int(lengths.get("pool", 256))
    prompts = quantile_lengths(lengths["prompt"], n)
    news = quantile_lengths(lengths["new_tokens"], n)
    order = np.random.default_rng(
        [int(lengths.get("shape_seed", 0)), 0]
    ).permutation(n)
    return [(prompts[i], news[int(j)]) for i, j in enumerate(order)]


def request_stream(mix: dict, seed: int, vocab_size: int) -> Iterator[Spec]:
    """Endless stream of requests for ``mix`` under ``seed``."""
    pool = length_pool(mix["lengths"])
    shape = np.random.default_rng(
        [int(mix["lengths"].get("shape_seed", 0)), 1]
    )
    rng = np.random.default_rng([int(seed), 0x7AFF1C])
    arrivals = mix["arrivals"]
    sharing = mix.get("sharing")
    prefixes = None
    if sharing:
        prefixes = [
            rng.integers(1, vocab_size, (int(sharing["prefix_tokens"]),))
            for _ in range(int(sharing["groups"]))
        ]
    clock = 0.0
    while True:
        for k in shape.permutation(len(pool)):
            prompt_len, new = pool[int(k)]
            prompt = rng.integers(1, vocab_size, (prompt_len,))
            if prefixes is not None:
                head = prefixes[int(shape.integers(len(prefixes)))]
                n = min(len(head), prompt_len - 1)
                prompt[:n] = head[:n]
            due = None if arrivals["kind"] == "backlog" else clock
            yield Spec(prompt.astype(np.int32), int(new), due)
            if due is not None:
                clock += _gap(arrivals, shape)


def _gap(arrivals: dict, rng) -> float:
    kind = arrivals["kind"]
    mean_gap = 1.0 / float(arrivals["rate_per_s"])
    if kind == "poisson":
        return float(rng.exponential(mean_gap))
    if kind == "gamma":
        # gaps with coefficient of variation cv: shape 1/cv^2
        shape = 1.0 / float(arrivals["cv"]) ** 2
        return float(rng.gamma(shape, mean_gap / shape))
    raise ValueError(f"unknown arrival process {kind!r}")
