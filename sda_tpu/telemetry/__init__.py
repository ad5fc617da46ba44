"""sda_tpu.telemetry — the measurement plane.

One process-global registry (counters / gauges / histograms with
thread-local write shards and a locked merge), lightweight spans with a
trace-id propagated client -> REST (``X-SDA-Trace``) -> service -> store,
a Prometheus text exposition (served at ``GET /v1/metrics``), and a
structured JSON log sink keyed by trace-id.

Module-level helpers front the global registry — instrumentation sites
do ``from .. import telemetry`` and call ``telemetry.counter(...)`` /
``telemetry.span(...)``. Everything honors the kill switch: start the
process with ``SDA_TELEMETRY=0`` (or call ``set_enabled(False)``) and
every operation becomes a branch-and-return no-op.

Metric names and label conventions are documented in
``docs/observability.md``; the snapshot/export surface is:

- ``snapshot()``     — merged dict of every series + recent spans (what
  ``bench.py`` banks as ``telemetry-<stamp>.json``);
- ``prometheus_text()`` — the ``/v1/metrics`` exposition body;
- ``spans(...)``     — recent span records for inspection/tests.
"""

from __future__ import annotations

from .prom import CONTENT_TYPE as PROMETHEUS_CONTENT_TYPE
from .prom import render as render_prometheus
from .registry import DEFAULT_BUCKETS, Counter, Gauge, Histogram, Registry
from .spans import (
    RING_RECORDS,
    TRACE_HEADER,
    SpanLog,
    current_trace_id,
    new_trace_id,
    sanitize_trace_id,
    set_trace_id,
    trace,
)
from .timeseries import TimeSeriesSampler, histogram_quantile, read_rss_mib

_REGISTRY = Registry()
_SPANS = SpanLog(_REGISTRY)


def get_registry() -> Registry:
    return _REGISTRY


def enabled() -> bool:
    return _REGISTRY.enabled


def set_enabled(value: bool) -> None:
    """Flip the whole plane on/off at runtime (bench overhead A/B, tests)."""
    _REGISTRY.enabled = bool(value)


def counter(name: str, help: str = "", **labels) -> Counter:
    return _REGISTRY.counter(name, help=help, **labels)


def gauge(name: str, help: str = "", **labels) -> Gauge:
    return _REGISTRY.gauge(name, help=help, **labels)


def histogram(name: str, help: str = "", buckets=DEFAULT_BUCKETS, **labels) -> Histogram:
    return _REGISTRY.histogram(name, help=help, buckets=buckets, **labels)


def span(name: str, **attrs):
    """Context manager: time a block, record a span carrying the current
    trace id."""
    return _SPANS.span(name, **attrs)


def spans(
    name: str | None = None,
    trace_id: str | None = None,
    since_mono: float | None = None,
    until_mono: float | None = None,
) -> list:
    """Finished span records, oldest first: by name prefix, by trace id, and
    by the interval of ``time.perf_counter()`` they start in."""
    return _SPANS.recent(
        name=name, trace_id=trace_id, since_mono=since_mono, until_mono=until_mono
    )


def snapshot(include_spans: int = 200) -> dict:
    """JSON-ready merged view: all series, metadata, and the newest
    ``include_spans`` span records, each with its monotonic ``start_mono``
    (``RING_RECORDS`` or more: the whole ring)."""
    snap = _REGISTRY.snapshot()
    out = {
        "enabled": _REGISTRY.enabled,
        "counters": [
            {"name": name, "labels": dict(labels), "value": value}
            for (name, labels), value in sorted(snap["counters"].items())
        ],
        "gauges": [
            {"name": name, "labels": dict(labels), "value": value}
            for (name, labels), value in sorted(snap["gauges"].items())
        ],
        "histograms": [
            {"name": name, "labels": dict(labels), **hist}
            for (name, labels), hist in sorted(snap["histograms"].items())
        ],
    }
    if include_spans:
        out["spans"] = _SPANS.recent()[-include_spans:]
    return out


def prometheus_text() -> str:
    return render_prometheus(_REGISTRY.snapshot())


def reset() -> None:
    """Zero every series and drop recorded spans (tests, bench reruns)."""
    _REGISTRY.reset()
    _SPANS.reset()


__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "Registry",
    "SpanLog",
    "TimeSeriesSampler",
    "histogram_quantile",
    "read_rss_mib",
    "DEFAULT_BUCKETS",
    "PROMETHEUS_CONTENT_TYPE",
    "RING_RECORDS",
    "TRACE_HEADER",
    "counter",
    "gauge",
    "histogram",
    "span",
    "spans",
    "snapshot",
    "prometheus_text",
    "render_prometheus",
    "get_registry",
    "enabled",
    "set_enabled",
    "reset",
    "trace",
    "set_trace_id",
    "current_trace_id",
    "new_trace_id",
    "sanitize_trace_id",
]
