"""The generator: the same seed gives the same requests, another seed
other token ids, and every seed the same sizes and arrivals."""

import itertools

import numpy as np

from benchmarks.harness import loading, traffic


def _take(mix, seed, n):
    return list(itertools.islice(
        traffic.request_stream(mix, seed, 50257), n))


def _mix(name="batch-saturated"):
    return loading.load_traffic(name, rehearse=False)


def test_same_seed_same_requests():
    a, b = _take(_mix(), 2 ** 31 + 11, 40), _take(_mix(), 2 ** 31 + 11, 40)
    for x, y in zip(a, b):
        assert x.new_tokens == y.new_tokens and x.due_s == y.due_s
        assert np.array_equal(x.prompt, y.prompt)


def test_other_seed_other_tokens_same_work():
    mix = _mix()
    n = mix["lengths"]["pool"]
    a, b = _take(mix, 1, 3 * n), _take(mix, 2, 3 * n)
    sizes = lambda specs: [(len(s.prompt), s.new_tokens) for s in specs]
    # the same sizes in the same order: the seed does not change the work
    assert sizes(a) == sizes(b)
    for k in range(3):  # each pass through the pool offers all of it
        assert sorted(sizes(a)[k * n:(k + 1) * n]) == sorted(
            traffic.length_pool(mix["lengths"]))
    assert sizes(a)[:n] != sizes(a)[n:2 * n]  # in another order
    assert not np.array_equal(a[0].prompt[:8], b[0].prompt[:8])


def test_lengths_stay_inside_the_engines_span():
    mix = _mix()
    pool = traffic.length_pool(mix["lengths"])
    lo, hi = mix["lengths"]["prompt"]["min"], mix["lengths"]["prompt"]["max"]
    assert all(lo <= p <= hi for p, _ in pool)
    assert max(p + n for p, n in pool) <= 1024
    import statistics

    assert abs(statistics.median(p for p, _ in pool) - 192) <= 2
    assert abs(statistics.median(n for _, n in pool) - 64) <= 2


def test_open_loop_arrivals_keep_their_rate_and_order():
    mix = _mix()
    mix["arrivals"] = {"kind": "poisson", "rate_per_s": 4.0}
    specs = _take(mix, 7, 2000)
    due = [s.due_s for s in specs]
    assert due == [s.due_s for s in _take(mix, 8, 2000)]  # any seed
    assert due[0] == 0.0 and due == sorted(due)
    assert abs(len(due) / due[-1] - 4.0) < 0.4
    mix["arrivals"] = {"kind": "gamma", "rate_per_s": 4.0, "cv": 3.0}
    gaps = np.diff([s.due_s for s in _take(mix, 7, 4000)])
    assert abs(gaps.std() / gaps.mean() - 3.0) < 0.6
