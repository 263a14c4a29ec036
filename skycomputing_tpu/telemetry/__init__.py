"""Unified telemetry: span tracer, metrics registry, live observability.

Pure stdlib — importable from every layer (parallel, runner, dynamics,
serving, fleet, tools) without pulling jax, and cheap enough to leave
wired in production code paths permanently (disabled tracing is a
``None`` check; an un-started exporter binds nothing).

- :mod:`.tracer` — span tracer + Chrome-trace export (+ recycled
  per-request lanes for end-to-end request waterfalls);
- :mod:`.metrics` — the one ``snapshot()`` contract over every stats
  surface, with counter/gauge field classification and per-source
  error isolation;
- :mod:`.timeseries` — bounded ring-buffered sampling with derived
  rates and windowed percentiles;
- :mod:`.exporter` — opt-in ``http.server`` endpoint: ``/metrics``
  (Prometheus text), ``/metrics.json``, ``/healthz``;
- :mod:`.slo` — declared SLO targets evaluated as multi-window burn
  rates, emitting ``slo_alert`` trace instants and a registry source;
- :mod:`.flight` — the always-on flight recorder: one bounded ring of
  structured events every subsystem's sanctioned tap feeds, with a
  replay-deterministic log + digest;
- :mod:`.incidents` — the incident plane: detector rules over the
  recorder + time-series, postmortem bundles stamped with digests;
- :mod:`.analysis` — trace analysis library (bubble/critical-path/
  serving breakdowns, per-request timeline reconstruction).
"""

from . import analysis
from .exporter import MetricsExporter
from .flight import FLIGHT_KINDS, FLIGHT_LANES, FlightEvent, FlightRecorder
from .incidents import (
    Incident,
    IncidentEngine,
    SEV_CRITICAL,
    SEV_INFO,
    SEV_WARNING,
    build_bundle,
    bundle_digest,
    cause_chain,
    chain_stages,
    default_rules,
    deterministic_bundle_view,
)
from .live import LiveMetricsMixin
from .metrics import MetricsRegistry
from .slo import SloAlert, SloMonitor, SloTarget
from .timeseries import MetricsTimeseries
from .tracer import (
    Tracer,
    disable_tracing,
    enable_tracing,
    get_tracer,
    span_sinks,
    trace_span,
)

__all__ = [
    "analysis",
    "FLIGHT_KINDS",
    "FLIGHT_LANES",
    "FlightEvent",
    "FlightRecorder",
    "Incident",
    "IncidentEngine",
    "LiveMetricsMixin",
    "MetricsExporter",
    "MetricsRegistry",
    "MetricsTimeseries",
    "SEV_CRITICAL",
    "SEV_INFO",
    "SEV_WARNING",
    "SloAlert",
    "SloMonitor",
    "SloTarget",
    "Tracer",
    "build_bundle",
    "bundle_digest",
    "cause_chain",
    "chain_stages",
    "default_rules",
    "deterministic_bundle_view",
    "disable_tracing",
    "enable_tracing",
    "get_tracer",
    "span_sinks",
    "trace_span",
]
