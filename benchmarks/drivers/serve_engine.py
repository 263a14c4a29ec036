"""Driver: serving through ``ServingEngine(kv_layout="paged")``.

Builds the GPT stack at the file's sizes with weights made on the device
from the seed, builds the engine as the file's ``engine`` group sets it,
warms one request per prefill bucket and the decode program, then drives
``engine.step()`` for the window under the cell's traffic mix:

- ``backlog``: a closed backlog; the driver keeps ``min_waiting``
  requests queued at all times, and the window opens once every decode
  row is occupied (plus ``ramp_ticks`` more ticks, so that the rows'
  ages are mixed).
- ``poisson`` / ``gamma``: an open loop on the wall clock; each request
  is submitted when it is due (never earlier), and timed from its due
  instant; the window opens ``ramp_s`` seconds after the first arrival.

Tokens are stamped by the driver after each ``engine.step()``: that is
when a caller of the engine can see them.  Correctness is decided
outside the window by the plain reference (``reference/gpt_forward``).
"""

from __future__ import annotations

import time

import numpy as np

from ..harness import peaks
from ..harness.runtime import dir_bytes
from ..harness.stats import (
    median,
    ms,
    percentile,
    token_gaps,
    tokens_in_window,
)
from ..harness.traffic import length_pool, request_stream
from ..reference import gpt_forward as reference

_COUNTERS = ("iterations", "prefill_waves", "prefill_tokens",
             "decode_tokens", "generated_tokens", "preemptions",
             "queue_stalls", "prefix_hits", "prefix_evictions",
             "prefill_s", "decode_s", "compiles")


def _seed_key(seed: int):
    import jax

    # any whole number: the low 32 bits seed, the rest is folded in
    return jax.random.fold_in(
        jax.random.key(seed % (1 << 32)), seed >> 32
    )


def build(config: dict, seed: int):
    """Stack and weights.  The weights are made on the device in ONE
    jitted call from the seed (a traced argument: every seed shares the
    program), in the type they are served in."""
    import jax

    from skycomputing_tpu.builder import build_layer_stack
    from skycomputing_tpu.models.gpt import GptConfig, gpt_layer_configs

    cfg = GptConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        intermediate_size=config["intermediate_size"],
        max_position_embeddings=config["max_position_embeddings"],
        hidden_act=config["hidden_act"], dtype=config["compute_dtype"],
    )
    layer_cfgs = gpt_layer_configs(cfg, deterministic=True)
    stack = build_layer_stack(layer_cfgs)
    probe = np.ones((1, 8), np.int32)
    params = jax.jit(lambda key: stack.init(key, probe))(_seed_key(seed))
    jax.block_until_ready(params)
    return cfg, layer_cfgs, stack, params


def run(ctx) -> dict:
    import jax

    from skycomputing_tpu.parallel.pipeline import xla_compile_count
    from skycomputing_tpu.serving import Request, ServingEngine
    from skycomputing_tpu.utils import enable_persistent_compilation_cache

    config, mix = ctx.config, ctx.traffic
    arrivals = mix["arrivals"]
    backlog = arrivals["kind"] == "backlog"
    cache_dir = enable_persistent_compilation_cache()

    t_build = time.perf_counter()
    cfg, layer_cfgs, stack, params = build(config, ctx.seed)
    param_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    options = dict(config["engine"])
    options["buckets"] = tuple(options["buckets"])
    engine = ServingEngine(layer_cfgs, params, **options)
    if not ctx.rehearse and engine.attn_impl != "pallas":
        raise RuntimeError(f"attn_impl on a TPU is {engine.attn_impl!r}")
    t_built = time.perf_counter()

    # warm-up: one request per prefill bucket, a few decode ticks each,
    # then one that shares a page and a half of prompt with the first, so
    # that the copy-on-write page copy is compiled too (two random
    # prompts of the window can share a first token by chance)
    warm_rng = np.random.default_rng(0)

    def warm(prompt):
        return Request(prompt=prompt.astype(np.int32),
                       max_new_tokens=int(mix["warm_new_tokens"]))

    prompts = [warm_rng.integers(1, cfg.vocab_size, (int(b),))
               for b in engine.bucketer.buckets]
    engine.run([warm(p) for p in prompts])
    sharer = warm_rng.integers(1, cfg.vocab_size, prompts[0].shape)
    head = engine.page_size * 3 // 2
    sharer[:head] = prompts[0][:head]
    engine.run([warm(sharer)])
    if engine.stats.cow_copies < 1:
        raise RuntimeError("warm-up made no copy-on-write page copy")
    t_warm = time.perf_counter()

    stream = request_stream(mix, ctx.seed, cfg.vocab_size)
    live = {}        # request_id -> [Request, tokens seen]
    stamps = {}      # request_id -> stamp of each token, in order
    due, late = {}, []
    ended = []       # (stamp, Request) of every terminal request
    pending = [next(stream)] if not backlog else []

    def submit(spec):
        r = engine.submit(Request(prompt=spec.prompt,
                                  max_new_tokens=spec.new_tokens))
        live[r.request_id] = [r, 0]
        stamps[r.request_id] = []
        return r

    def pump(now, t_first):
        """Offer load: top the backlog up, or submit what is due."""
        if backlog:
            while engine.stats.queue_depth < arrivals["min_waiting"]:
                submit(next(stream))
            return
        while pending[0].due_s <= now - t_first:
            spec = pending.pop(0)
            r = submit(spec)
            due[r.request_id] = t_first + spec.due_s
            late.append(now - due[r.request_id])
            pending.append(next(stream))

    def stamp(now):
        for rid in list(live):
            r, seen = live[rid]
            n = len(r.tokens)
            if n > seen:
                stamps[rid].extend([now] * (n - seen))
                live[rid][1] = n
            if r.status not in ("queued", "running"):
                ended.append((now, r))
                del live[rid]

    def tick(t_first):
        """One engine iteration as a caller sees it; returns its end."""
        if not engine.has_work():
            # open loop, nothing in flight: wait for the next arrival
            wait = t_first + pending[0].due_s - time.perf_counter()
            if wait > 0:
                time.sleep(min(wait, 0.002))
        else:
            with ctx.tracer.mark():
                engine.step()
        now = time.perf_counter()
        stamp(now)
        pump(now, t_first)
        return now

    # ramp, outside the window
    t_first = time.perf_counter()
    pump(t_first, t_first)
    if backlog:
        now = tick(t_first)
        while engine.free_slots > 0:
            now = tick(t_first)
        for _ in range(int(mix.get("ramp_ticks", 0))):
            now = tick(t_first)
    else:
        now = t_first
        while now - t_first < float(mix.get("ramp_s", 0.0)):
            now = tick(t_first)

    # the measured window
    t_open = now
    opened = {k: getattr(engine.stats, k) for k in _COUNTERS}
    loads0, compiles0 = ctx.loads.count, xla_compile_count()
    ctx.tracer.open(t_open)
    pages_peak, depth_mid, ticks = 0, None, 0
    while now - t_open < ctx.seconds:
        ctx.tracer.poll(now)
        now = tick(t_first)
        ticks += 1
        pages_peak = max(pages_peak, engine.stats.pages_in_use)
        if depth_mid is None and now - t_open >= ctx.seconds / 2:
            depth_mid = engine.stats.queue_depth
    t_close = now
    ctx.tracer.stop()
    loads = ctx.loads.count - loads0
    compiles = xla_compile_count() - compiles0
    delta = {k: getattr(engine.stats, k) - opened[k] for k in _COUNTERS}
    depth_end = engine.stats.queue_depth
    window = (t_open, t_close)

    # ---- outside the window: reduce, then decide `correct` ---------------
    reduced = ctx.tracer.reduce()
    done_in = [r for t, r in ended if t_open < t <= t_close]
    failed = [r for r in done_in if r.status != "finished"]
    engine._pool.check_consistency()
    finished = [r for r in done_in if r.status == "finished"]
    if len(finished) < mix["check_streams"]:
        # a short window sees few finish: take earlier ones too
        finished += [r for t, r in ended
                     if t <= t_open and r.status == "finished"]
    pick = np.random.default_rng([ctx.seed, 0xC0FFEE]).permutation(
        len(finished))[: mix["check_streams"]]
    sample = [finished[int(i)] for i in pick]
    t_check = time.perf_counter()
    gaps_of = reference.make_argmax_gaps(stack, engine.max_len)
    worst = []
    for i in range(0, len(sample), 2):  # two streams at a time
        pair = sample[i: i + 2]
        while len(pair) < 2:            # one shape for the reference
            pair.append(pair[0])
        got = gaps_of(params, [np.asarray(r.output()) for r in pair],
                      [int(r.prompt.size) for r in pair])
        worst += [float(g.max()) for g in got]
    check_s = time.perf_counter() - t_check
    correct = (
        bool(sample) and max(worst) <= reference.TIE_ULPS
        and not failed and loads == 0 and compiles == 0
    )

    gaps = token_gaps(stamps, window)
    emitted = tokens_in_window(stamps, window)
    live_tokens = sum(r.index for r in engine.running_requests)
    pool = length_pool(mix["lengths"])
    tick_bytes = peaks.gpt_decode_tick_bytes(
        param_bytes=param_bytes, hidden_size=cfg.hidden_size,
        num_layers=cfg.num_hidden_layers, live_tokens=live_tokens,
        kv_bytes_per_value=2,
    )
    ctx.emit(
        event="serve_window", window_s=t_close - t_open, ticks=ticks,
        tokens_emitted=emitted, requests_ended=len(done_in),
        requests_failed=len(failed), stats_delta=delta,
        tpot_ms=dict(samples=len(gaps),
                     median=ms(median(gaps)), p95=ms(percentile(gaps, 95)),
                     p99=ms(percentile(gaps, 99)),
                     max=ms(max(gaps) if gaps else None)),
        queue_depth_mid=depth_mid, queue_depth_end=depth_end,
        pages_in_use_peak=pages_peak, num_pages=engine.num_pages,
        rows=engine.max_concurrency, attn_impl=engine.attn_impl,
        live_tokens_at_close=live_tokens, param_bytes=param_bytes,
        decode_tick_least_bytes=tick_bytes,
        lengths_pool=dict(
            size=len(pool),
            prompt_mean=float(np.mean([p for p, _ in pool])),
            new_tokens_mean=float(np.mean([n for _, n in pool])),
        ),
        generator_late_ms=dict(samples=len(late),
                               p95=ms(percentile(late, 95))),
        programs_loaded_or_compiled_in_window=loads,
        xla_compiles_in_window=compiles,
        checked_streams=len(sample), worst_gap_ulps=worst,
        allowed_gap_ulps=reference.TIE_ULPS, check_s=check_s,
        setup_parts_s=dict(build=t_built - t_build, warm=t_warm - t_built,
                           ramp=t_open - t_warm, before_build=t_build - ctx.t0),
        compile_cache=cache_dir, compile_cache_bytes=dir_bytes(cache_dir),
    )
    return dict(
        kind="serve", correct=correct,
        attempted=len(done_in), failed=len(failed),
        setup_s=t_open - ctx.t0, window_s=t_close - t_open,
        tokens_emitted=emitted, token_gaps_s=gaps, ticks=ticks,
        stats_delta=delta, pages_in_use_peak=pages_peak,
        num_pages=engine.num_pages, rows=engine.max_concurrency,
        queue_depth_mid=depth_mid, queue_depth_end=depth_end,
        due=due, stamps=stamps, window=window, generator_late_s=late,
        lost=[r.request_id for _, r in ended if r.status != "finished"],
        trace=reduced,
    )
