"""What every driver needs around its measured window: the line printer,
the count of programs compiled or loaded, the device as JAX reports it,
the profiler session, and the last line."""

from __future__ import annotations

import json
import os
import shutil
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

from . import trace as trace_mod


def emit(**record) -> None:
    """An earlier line: one JSON object, for whoever reads the run."""
    print(json.dumps(record, default=str), flush=True)


class ProgramLoads:
    """Counts every executable the backend compiled OR loaded from the
    persistent cache.  ``xla_compile_count()`` of the program leaves the
    cache's hits out (rightly, for its purpose); inside a measured window
    neither may happen, because a load stalls the step as a compile does,
    only for less long."""

    def __init__(self):
        self.count = 0
        from jax import monitoring

        def on_duration(name: str, _secs: float, **_kw) -> None:
            if name == "/jax/core/compile/backend_compile_duration":
                self.count += 1

        monitoring.register_event_duration_secs_listener(on_duration)


def claim_devices(chips: int, rehearse: bool):
    """``jax.devices()``, or None (with the reason on stderr) unless they
    are TPUs and as many as the cell asks for.  A rehearsal is a CPU run
    by construction, whatever is attached."""
    import sys

    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={chips}"
            ).strip()
    import jax

    devices = jax.devices()
    if not rehearse and devices[0].platform != "tpu":
        print(f"benchmark: no TPU; JAX reports {len(devices)} x "
              f"{devices[0].platform} ({devices[0].device_kind})",
              file=sys.stderr)
        return None
    if len(devices) < chips:
        print(f"benchmark: needs {chips} chips, JAX reports {len(devices)}",
              file=sys.stderr)
        return None
    return devices


def device_record(devices, chips: int) -> dict:
    """``device`` of the last line, as JAX reports it; the peak is that
    of the fullest chip used."""
    peaks = []
    for dev in devices[:chips]:
        stats = dev.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return dict(
        platform=devices[0].platform,
        kind=devices[0].device_kind,
        count=len(devices),
        memory_peak_bytes=max(peaks) if peaks else None,
    )


def dir_bytes(path: Optional[str]) -> Optional[int]:
    if not path or not os.path.isdir(path):
        return None
    return sum(
        os.path.getsize(os.path.join(base, name))
        for base, _, names in os.walk(path) for name in names
    )


@dataclass
class TraceSession:
    """Profiles a steady sub-window at the END of the measured window:
    ``poll(now)`` is called between iterations and starts the profiler
    when its time has come; the driver calls ``stop()`` once the window
    has closed, because stopping takes seconds to tens of seconds (it
    writes the trace out) and must not be counted as a step."""

    enabled: bool
    out_dir: str
    window_s: float
    max_trace_s: float = 2.5
    state: str = "idle"      # idle -> tracing -> done
    t_open: Optional[float] = None

    def open(self, t_open: float) -> None:
        self.t_open = t_open
        self.begin = self.window_s - min(self.max_trace_s,
                                         self.window_s / 4.0)

    def poll(self, now: float) -> None:
        if not self.enabled or self.t_open is None:
            return
        if self.state == "idle" and now - self.t_open >= self.begin:
            import jax

            shutil.rmtree(self.out_dir, ignore_errors=True)
            os.makedirs(self.out_dir, exist_ok=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # spans come from the driver
            options.host_tracer_level = 1    # TraceMe/TraceAnnotation only
            jax.profiler.start_trace(self.out_dir, profiler_options=options)
            self.state = "tracing"

    def stop(self) -> None:
        if self.state == "tracing":
            import jax

            jax.profiler.stop_trace()
            self.state = "done"

    def mark(self):
        """The annotation a driver puts around each iteration."""
        import contextlib

        if self.state != "tracing":
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(trace_mod.MARK)

    def reduce(self) -> Optional[dict]:
        """Outside the window: read the trace and reduce it."""
        self.stop()
        if self.state != "done":
            return None
        return trace_mod.reduce_events(
            trace_mod.read_xplane(trace_mod.find_xplane(self.out_dir))
        )


@dataclass
class Context:
    """What ``run.py`` hands a driver."""

    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    rehearse: bool
    t0: float                      # process start, on time.perf_counter
    root: str
    out_dir: str                   # <checkout>/.bench_out/<cell>
    loads: ProgramLoads
    tracer: TraceSession
    emit: Callable[..., None] = emit
    devices: List[Any] = field(default_factory=list)


def last_line(record: dict, metrics: Dict[str, dict], device: dict) -> str:
    line = dict(
        correct=bool(record["correct"]),
        attempted=int(record["attempted"]),
        failed=int(record["failed"]),
        metrics=metrics,
        device=device,
    )
    reduced = record.get("trace")  # None unless a chip run was traced
    if reduced:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        line["breakdown"] = dict(
            device_ops=reduced["device_ops"][:10],
            idle_gaps=reduced["idle_gaps"][:10],
        )
    return json.dumps(line)
