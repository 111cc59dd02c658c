# -*- coding: utf-8 -*-
"""
Unified observability layer: spans, the structured event log, request
timelines, and the Prometheus exporter.

Grown from the reference's ``measure`` decorator (reference
functions.py:24-41) and the in-process ``MetricsRegistry``
(utils/tracing.py) into a real subsystem — see each submodule:

- :mod:`~distributed_dot_product_tpu.obs.spans` — nestable host-side
  wall-time spans with a zero-overhead disabled path.
- :mod:`~distributed_dot_product_tpu.obs.events` — append-only
  schema-versioned JSONL event log (serve/train/health/fault lifecycle
  vocabulary), crash-safe flushing, size-based rotation.
- :mod:`~distributed_dot_product_tpu.obs.timeline` — per-request
  lifecycle reconstruction over the event log (multi-replica log sets
  merge through ``events.merge_events``).
- :mod:`~distributed_dot_product_tpu.obs.slo` — goodput-under-SLO
  accounting from the event log alone (SloSpec, per-tenant breakdowns,
  the ``slo check`` CI gate against ``SLO_BASELINE.json``).
- :mod:`~distributed_dot_product_tpu.obs.exporter` — Prometheus-text
  rendering of the metrics registry plus the optional ``/metrics`` +
  ``/healthz`` + ``/profile`` HTTP thread (off by default).
- :mod:`~distributed_dot_product_tpu.obs.devmon` — live device-memory
  telemetry gauges and guarded on-demand ``jax.profiler`` captures.
- :mod:`~distributed_dot_product_tpu.obs.flight` — the incident flight
  recorder: a hard-bounded black-box ring teeing the event log +
  metric/device samples, dumped as schema-versioned post-mortem
  bundles on stall / exception / NaN-storm / anomaly / SIGTERM /
  ``GET /dump``.
- :mod:`~distributed_dot_product_tpu.obs.anomaly` — pluggable online
  detectors (EWMA z-score, static threshold, rate-of-change) over the
  registry's metric streams, emitting ``anomaly.detected`` events and
  chaining profile captures / flight dumps.
- :mod:`~distributed_dot_product_tpu.obs.doctor` — post-mortem bundle
  diagnosis (``python -m distributed_dot_product_tpu.obs doctor
  BUNDLE``): classify the incident and name affected tenants/requests
  from the bundle alone.

CLI: ``python -m distributed_dot_product_tpu.obs validate <log.jsonl>``
schema-checks a log offline; ``... stats <log.jsonl>`` summarizes it
operationally; ``... timeline <log.jsonl> <request-id>`` prints one
request's reconstructed lifecycle (scripts/ci.sh and
scripts/smoke_serve.sh drive them).
"""

from distributed_dot_product_tpu.obs.devmon import (  # noqa: F401
    CaptureInFlight, DeviceMonitor, ProfileCapture,
    device_stats_snapshot,
)
from distributed_dot_product_tpu.obs.anomaly import (  # noqa: F401
    AnomalyWatchdog, EwmaZScore, RateOfChange, StaticThreshold, Watch,
    default_watches,
)
from distributed_dot_product_tpu.obs.events import (  # noqa: F401
    EVENT_SCHEMA, SCHEMA_VERSION, EventLog, activate, emit, get_active,
    merge_events, open_from_env, read_events, remove_log, set_active,
    validate_file,
)
from distributed_dot_product_tpu.obs.flight import (  # noqa: F401
    FlightRecorder, load_bundle,
)
from distributed_dot_product_tpu.obs.slo import (  # noqa: F401
    SloReport, SloSpec, check_baseline, goodput,
)
from distributed_dot_product_tpu.obs.exporter import (  # noqa: F401
    MetricsServer, render_prometheus,
)
from distributed_dot_product_tpu.obs.spans import (  # noqa: F401
    DEVICE_SCOPES, SpanCollector, SpanRecord, collecting, device_scope,
    enable, enabled, get_collector, span, spanned,
)
from distributed_dot_product_tpu.obs.timeline import (  # noqa: F401
    Timeline, reconstruct, timeline,
)

__all__ = [
    'EVENT_SCHEMA', 'SCHEMA_VERSION', 'EventLog', 'activate', 'emit',
    'get_active', 'merge_events', 'open_from_env', 'read_events',
    'remove_log', 'set_active', 'validate_file', 'SloReport', 'SloSpec',
    'check_baseline', 'goodput', 'MetricsServer', 'render_prometheus',
    'SpanCollector', 'SpanRecord', 'collecting', 'enable', 'enabled',
    'get_collector', 'span', 'spanned', 'DEVICE_SCOPES', 'device_scope',
    'Timeline', 'reconstruct',
    'timeline', 'CaptureInFlight', 'DeviceMonitor', 'ProfileCapture',
    'device_stats_snapshot', 'FlightRecorder', 'load_bundle',
    'AnomalyWatchdog', 'EwmaZScore', 'RateOfChange', 'StaticThreshold',
    'Watch', 'default_watches',
]
