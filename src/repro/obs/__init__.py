"""repro.obs — unified observability: metrics, mechanism telemetry, tracing.

One registry (`default_registry`), one switch (`enabled`/`set_enabled`),
zero effect on results: the obs layer reads the traces the drivers
already return and annotates phases with pure-metadata profiler scopes.
Enabled-vs-disabled outputs are bitwise identical (tests/test_obs.py).

Layer map (DESIGN.md §8):

* `metrics` — Counter / Gauge / log-bucketed Histogram + MetricsRegistry
  (snapshot dict, JSON, Prometheus text).
* `telemetry` — MechanismTelemetry records aggregated host-side from
  the drivers' stacked scan traces (overflow rate, scored rows, √m
  ratio); published per run.
* `trace` — `scope` (in-graph named_scope) / `annotate` (host-side
  TraceAnnotation with integer ids), both gated on the obs switch.
* `events` — monotonic-stamped EventSink (elastic fail/recover, …).
* `clock` — the single sanctioned `time` import in `src/`.
"""

from repro.obs import clock
from repro.obs.events import EventSink, ObsEvent, default_sink
from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    default_registry,
    reset_default_registry,
)
from repro.obs.telemetry import (
    MechanismTelemetry,
    aggregate_traces,
    publish,
    record_run,
)
from repro.obs.trace import annotate, disabled, enabled, scope, set_enabled

__all__ = [
    "clock",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "default_registry",
    "reset_default_registry",
    "MechanismTelemetry",
    "aggregate_traces",
    "publish",
    "record_run",
    "EventSink",
    "ObsEvent",
    "default_sink",
    "annotate",
    "scope",
    "enabled",
    "set_enabled",
    "disabled",
]
