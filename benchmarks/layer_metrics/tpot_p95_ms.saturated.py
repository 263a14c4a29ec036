"""Layer: serving engine, scheduler.  95th percentile, over EVERY token
after a request's first that was emitted in the window, of the time
since that request's previous token; stamped by the driver after each
``engine.step()``, so a prefill wave that stalls the other rows shows.

A per-layer metric in the saturated cell, not an end-to-end one: a 20 s
window holds 66 ticks, so the top twentieth is three or four ticks, and
the percentile flips between two of them (422 or 474 ms: one seed of six
read the higher value in both of its runs; my chip runs, PR 24).  Under
a standing backlog the gap between tokens is the tick plus whatever
prefill waves shared the step; what the job's owner pays for is tokens
per second.  An open-loop cell below capacity is where this tail is an
end-to-end metric."""

from benchmarks.harness.stats import percentile


def read(record):
    if record.get("kind") != "serve" or not record.get("token_gaps_s"):
        return None
    return percentile(record["token_gaps_s"], 95.0) * 1e3
