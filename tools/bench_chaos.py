#!/usr/bin/env python
"""Chaos bench: named fault campaigns replayed and audited, gated.

The chaos plane (``skycomputing_tpu/chaos/``) makes fault campaigns
values — seeded, digestible, paired with a workload-catalog scenario.
This bench is where those values meet a real fleet and produce a
committed verdict (``BENCH_chaos.json``).  Every catalog plan runs
through the same harness:

- **reference**: the plan's paired scenario on a fault-free fleet of
  the plan's shape — the token-identity baseline;
- **faulted**: the byte-identical trace with the plan's
  :class:`~skycomputing_tpu.chaos.FaultInjector` attached, then an
  idle epilogue of ``recovery_budget_ticks + 10`` so recovery lands
  inside the replay;
- **faulted, again**: the same seed end to end — the determinism run.

Gates, written into the artifact per plan:

- the whole-run invariant audit passes: zero lost or duplicated
  tokens, every terminal state reasoned, admitted streams
  token-identical to the fault-free reference, page/refcount + slot
  consistency on every live engine, monotonic counters, and
  time-to-healthy within the plan's ``recovery_budget_ticks``;
- both replays saw the same trace (``digest`` equality — the workload
  plane's replayability is itself a gate);
- at least one fault APPLIED (a campaign that never landed proves
  nothing);
- the two same-seed faulted runs produced byte-identical fault event
  logs and equal audit digests (double-run determinism: the chaos
  plane's own replayability promise).

Usage::

    python tools/bench_chaos.py --list
    python tools/bench_chaos.py --out BENCH_chaos.json
    python tools/bench_chaos.py --plan reform_flap
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
from typing import Optional

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_by_path(name: str, *parts: str):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_ROOT, *parts)
    )
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def _catalog():
    """The fault-plan catalog, loadable on a bare runner: the registry
    lives inside the self-contained stdlib module ``plan.py``."""
    try:
        from skycomputing_tpu.chaos import plan as catalog
        return catalog
    except Exception:  # pragma: no cover - exercised on bare runners
        return _load_by_path(
            "_skytpu_chaos_plan",
            "skycomputing_tpu", "chaos", "plan.py",
        )


def list_plans() -> int:
    catalog = _catalog()
    for name in catalog.fault_plan_names():
        p = catalog.get_fault_plan(name)
        print(f"{name:20s} events={len(p.events):2d} "
              f"scenario={p.scenario:18s} replicas={p.replicas} "
              f"budget={p.recovery_budget_ticks:3d}t  {p.description}")
    return 0


# --------------------------------------------------------------------------
# full mode: plan replays, audited
# --------------------------------------------------------------------------


def run_bench(plan_names, out: Optional[str], seed: int) -> int:
    if _ROOT not in sys.path:
        sys.path.insert(0, _ROOT)
    import time

    import jax
    import numpy as np

    from skycomputing_tpu.builder import build_layer_stack
    from skycomputing_tpu.chaos import (
        FaultInjector,
        audit_run,
        get_fault_plan,
        make_probe,
    )
    from skycomputing_tpu.disagg import DisaggFleet
    from skycomputing_tpu.fleet import (
        FleetAutoscaler,
        FleetSupervisor,
        ServingFleet,
    )
    from skycomputing_tpu.models.gpt import GptConfig, gpt_layer_configs
    from skycomputing_tpu.serving import Request
    from skycomputing_tpu.telemetry.slo import SloMonitor, SloTarget
    from skycomputing_tpu.workload import ScenarioPlayer, get_scenario

    cfg = GptConfig(vocab_size=512, hidden_size=64,
                    num_hidden_layers=2, num_attention_heads=2,
                    max_position_embeddings=160, dropout_prob=0.0,
                    dtype="float32")
    layer_cfgs = gpt_layer_configs(cfg, deterministic=True)
    stack = build_layer_stack(layer_cfgs)
    print(f"initializing {len(layer_cfgs)}-layer GPT "
          f"(hidden={cfg.hidden_size})...", flush=True)
    params = stack.init(jax.random.key(seed),
                        np.ones((1, 8), np.int32))

    buckets = (32, 64, 96)
    engine_kwargs = dict(num_slots=2, max_len=128, buckets=buckets,
                         prefill_batch=1, kv_layout="paged",
                         page_size=8)

    def make_fleet(plan):
        auto = None
        if plan.autoscale:
            auto = FleetAutoscaler(
                min_replicas=1, max_replicas=max(3, plan.replicas),
                up_streak=3, down_streak=6, cooldown_ticks=8,
                slack_utilization=0.35,
            )
        supervisor = FleetSupervisor(check_every=1,
                                     heartbeat_misses=1,
                                     sick_threshold=8.0, k_checks=3)
        if plan.disagg:
            # disagg campaigns run one prefill specialist plus
            # replicas-1 decoders, so a plan's index:0 selector always
            # names the prefill side (the kill-mid-handoff target)
            fleet = DisaggFleet(
                layer_cfgs, params,
                prefill_replicas=1,
                decode_replicas=plan.replicas - 1,
                engine_kwargs=dict(engine_kwargs),
                supervisor=supervisor,
                autoscaler=auto,
            )
        else:
            fleet = ServingFleet(
                layer_cfgs, params, replicas=plan.replicas,
                engine_kwargs=dict(engine_kwargs),
                supervisor=supervisor,
                autoscaler=auto,
            )
        if auto is not None:
            # the autoscaler's burn signal (the bench_scenarios
            # queue_pressure target): without a monitor it can only
            # ever scale DOWN
            # threshold 2: a replica's decode rows float with its
            # pages, so the scenario's peak produces a shallow queue
            fleet.attach_slo(SloMonitor([
                SloTarget(name="queue_pressure",
                          metric="fleet.queue_depth",
                          threshold=2, budget=0.25,
                          fast_window=1, slow_window=8),
            ]))
        return fleet

    # compile warmup once: every fleet shares the stage-program cache,
    # so the first fleet pays the bucket compiles for all of them
    warm_plan = get_fault_plan(plan_names[0], seed=seed)
    warm_fleet = make_fleet(warm_plan)
    warm_fleet.run([
        Request(prompt=np.full((b - 2,), b + 1, np.int32),
                max_new_tokens=2) for b in buckets
    ])

    def replay(plan, scenario, injector):
        fleet = make_fleet(plan)
        if injector is not None:
            fleet.fault_injector = injector
        probe = make_probe(fleet)
        player = ScenarioPlayer(scenario, fleet, sample_fn=probe)
        report = player.play()
        # idle epilogue: recovery (and autoscaler drains) land inside
        # the replay, exactly as a production loop would keep ticking
        for _ in range(plan.recovery_budget_ticks + 10):
            fleet.step()
            report.timeline.append(probe())
        return fleet, report

    plans, all_passed = {}, True
    for name in plan_names:
        plan = get_fault_plan(name, seed=seed)
        t0 = time.perf_counter()
        print(f"running {name} (scenario {plan.scenario}, "
              f"{plan.replicas} replicas"
              f"{', autoscaled' if plan.autoscale else ''})...",
              flush=True)

        def trace():
            return get_scenario(plan.scenario, seed=plan.scenario_seed,
                                rate_scale=plan.rate_scale,
                                ticks_scale=plan.ticks_scale)

        ref_fleet, ref_report = replay(plan, trace(), None)
        inj_a = FaultInjector(plan)
        fleet_a, rep_a = replay(plan, trace(), inj_a)
        audit_a = audit_run(fleet_a, rep_a, reference=ref_report,
                            injector=inj_a)
        # the determinism run: same seed end to end, fresh fleet
        inj_b = FaultInjector(plan)
        fleet_b, rep_b = replay(plan, trace(), inj_b)
        audit_b = audit_run(fleet_b, rep_b, reference=ref_report,
                            injector=inj_b)

        applied = [e for e in inj_a.event_log() if e["ok"]]
        gates = {c.name: bool(c.ok) for c in audit_a.checks}
        gates.update(
            workload_replayable=bool(
                rep_a.digest == ref_report.digest
            ),
            faults_applied=bool(applied),
            event_log_deterministic=bool(
                inj_a.deterministic_log() == inj_b.deterministic_log()
                and audit_a.digest() == audit_b.digest()
            ),
        )
        passed = all(gates.values())
        all_passed = all_passed and passed
        wall_s = time.perf_counter() - t0
        plans[name] = dict(
            plan=plan.to_dict(),
            plan_digest=plan.digest(),
            trace_digest=rep_a.digest,
            summary=rep_a.summary(),
            reference_summary=ref_report.summary(),
            event_log=inj_a.event_log(),
            recoveries=list(inj_a.recoveries),
            audit=audit_a.to_dict(),
            audit_digest=audit_a.digest(),
            fleet_stats=fleet_a.stats.snapshot(),
            quarantined={
                n: dict(q)
                for n, q in fleet_a.supervisor.quarantined.items()
            },
            gates=gates,
            passed=passed,
            wall_s=round(wall_s, 3),
        )
        failed = [g for g, ok in gates.items() if not ok]
        print(f"  {name}: {'PASS' if passed else 'FAIL'} "
              f"({len(applied)}/{len(inj_a.event_log())} events "
              f"applied, "
              f"{plans[name]['summary']['total']['finished']} "
              f"finished, {wall_s:.1f}s"
              f"{'' if passed else ', failed: ' + ', '.join(failed)})",
              flush=True)

    report_doc = dict(
        bench="chaos_fault_plans",
        device_kind=jax.devices()[0].device_kind,
        model=dict(cfg.to_dict()),
        fleet=dict(engine_kwargs),
        seed=seed,
        notes=(
            "each plan replays its paired scenario three times: a "
            "fault-free reference, the faulted run the audit judges, "
            "and a same-seed determinism run whose event log and "
            "audit digest must match byte for byte; event logs carry "
            "no request ids or wall times by construction"
        ),
        plans=plans,
        passed=all_passed,
    )
    if out:
        with open(out, "w") as f:
            json.dump(report_doc, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote {out}")
    print(f"chaos bench: {'PASS' if all_passed else 'FAIL'}")
    return 0 if all_passed else 1


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--list", action="store_true",
                        help="list the fault-plan catalog and exit")
    parser.add_argument("--plan", default=None,
                        help="run one named plan (default: the whole "
                             "catalog)")
    parser.add_argument("--out", default=None,
                        help="write the JSON artifact here")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()
    if args.list:
        return list_plans()
    catalog = _catalog()
    names = ([args.plan] if args.plan
             else catalog.fault_plan_names())
    for name in names:
        if name not in catalog.fault_plan_names():
            raise SystemExit(
                f"unknown fault plan {name!r}; catalog: "
                f"{catalog.fault_plan_names()}"
            )
    return run_bench(names, args.out, args.seed)


if __name__ == "__main__":
    sys.exit(main())
