"""The arithmetic behind the Nemotron-H cell's metrics, on small made-up
inputs: device time by scope, device time by stage, operation counts."""

import json
import os

from benchmarks.harness import loading, nemotron_h_counts as counts
from benchmarks.harness import scoped_ops, stage_busy

CONFIG = loading.load_json(os.path.join(
    loading.BENCH_DIR, "configs", "nemotron-3-nano-30b-a3b.json"))


def test_time_by_scope_joins_trace_names_with_the_programs_list():
    rows = [
        ("jit_bwd", "%fusion.1", "f32[8,8]{1,0:T(8,128)}", "ssd_scan"),
        ("jit_bwd", "%gmm.2", "bf16[64,32]{1,0}", "moe_experts"),
        ("jit_bwd", "%fusion.3", "bf16[64,32]{1,0}", "moe_experts"),
        ("jit_bwd", "%fusion.4", "f32[4]{0}", ""),
        # two programs called jit_bwd, one instruction name, two scopes
        ("jit_bwd", "%fusion.5", "f32[4]{0}", "ssd_scan"),
        ("jit_bwd", "%fusion.5", "f32[4]{0}", ""),
        ("jit_fwd", "%fusion.1", "f32[8,8]{1,0}", "gqa_attn"),
    ]
    times = {
        "jit_bwd/%fusion.1 = f32[8,8] fusion(f32[8] %x), kind=kLoop": 1.0,
        "jit_bwd/%gmm.2 = bf16[64,32] custom-call(bf16[64,16] %a)": 2.0,
        "jit_bwd/%fusion.3 = bf16[64,32] fusion(bf16[64,32] %b)": 4.0,
        "jit_bwd/%fusion.4 = f32[4] fusion()": 8.0,
        "jit_bwd/%fusion.5 = f32[4] fusion()": 16.0,
        "jit_fwd/%fusion.1 = f32[8,8] fusion()": 32.0,
        "jit_update/%fusion.1 = f32[8,8] fusion()": 64.0,
    }
    got = scoped_ops._join(times, rows)
    assert got["seconds"] == {"ssd_scan": 1.0, "moe_experts": 6.0,
                              "gqa_attn": 32.0}
    assert got["custom_call_seconds"] == {"moe_experts": 2.0}
    assert got["ambiguous_s"] == 16.0
    assert scoped_ops.by_scope(dict(trace=None)) is None
    assert scoped_ops.by_scope(dict(trace={"op_time_by_name": times},
                                    scoped_instructions=None)) is None


def test_time_by_stage_follows_the_issue_order():
    S, M, t, events = 3, 2, 0, []

    def ran(name, dur):
        nonlocal t
        events.append(dict(name=f"{name}(123)", start_ns=t, dur_ns=dur))
        t += dur + 5

    for _ in range(2):                       # two steps
        for _ in range(M):
            ran("jit_fwd", 1_000)            # stage 0
            ran("jit_fwd_counted", 2_000)    # stage 1
            ran("jit_fwd", 4_000)            # stage 2
        for m in range(M):
            ran("jit_loss_and_dlogits", 100)
            for dur, name in ((40_000, "jit_bwd"), (20_000, "jit_bwd"),
                              (10_000, "jit_bwd_params_only")):
                ran(name, dur)               # stages 2, 1, 0
                if m:
                    ran("jit_grad_add", dur // 100)
        for dur in (7, 70, 700):
            ran("jit_update", dur)
        ran("jit__lambda", 9_999)            # something else: no stage's
    busy = stage_busy.by_stage(events, [1] * S)
    per_step = [M * 1_000 + M * 10_000 + 100 + 7,
                M * 2_000 + M * 20_000 + 200 + 70,
                M * 4_000 + M * 40_000 + 400 + 700 + M * 100]
    assert busy == [2 * x / 1e9 for x in per_step]
    assert stage_busy.by_stage([], [1] * S) is None
    # two stages, the first running its two layers as a program each
    layered = stage_busy.by_stage(events, [2, 1])
    assert layered == [busy[0] + busy[1], busy[2]]


def test_counts_redo_the_issues_arithmetic():
    parts = counts.forward_flops_by_part(
        CONFIG, seq=4096, pairs_a_layer=4096 * 6 * 8 / 128)
    tera = {k: round(v / 1e12, 2) for k, v in parts.items()}
    assert tera == dict(
        mamba_projections=1.27, ssd_scan=0.06, router=0.01,
        shared_expert=0.65, routed_experts=0.12,
        attention_projections=0.19, attention_scores=0.14, head=0.36)
    step = counts.train_step_flops(CONFIG, batch=4, seq=4096,
                                   pairs_a_layer=1536)
    assert step == 12 * sum(parts.values())
    peaks = dict(bf16_flops=197e12, hbm_bytes_per_s=819e9)
    shape = dict(tokens=4096, heads=64, head_dim=64, groups=8, state=128)
    scan = counts.least_seconds(
        counts.ssd_scan_forward_flops(chunk=128, **shape),
        counts.ssd_scan_forward_bytes(**shape), peaks)
    assert 0.9e-4 < scan < 1.1e-4            # the bytes bound it
    gmm = dict(pairs=1536, d_in=2688, d_out=1856)
    one = counts.least_seconds(
        counts.gmm_call_flops(**gmm),
        counts.gmm_call_bytes(experts=8, **gmm), peaks)
    assert counts.gmm_call_flops(**gmm) / 197e12 < one   # bytes again


def test_the_configuration_file_keeps_every_published_width():
    rows = [json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")] \
        if os.path.exists(
            "/opt/skills/guides/model-configs/architectures.jsonl") else []
    published = next((r["config"] for r in rows
                      if r["source_url"] == CONFIG["source"]), None)
    if published is None:
        return
    differ = {k for k, v in published.items() if CONFIG.get(k) != v}
    assert differ == set(CONFIG["reduced"])
    assert not [k for k in CONFIG["reduced"]
                if k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"]
