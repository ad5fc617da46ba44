"""Lightweight spans with a propagated trace-id.

A *trace-id* is an opaque hex token that follows one logical operation
across layers: the client stamps it on every HTTP request
(``X-SDA-Trace``), the REST server adopts it for the handler thread, and
every ``span()`` recorded below — service, stores, crypto — carries it.
Propagation rides a ``contextvars.ContextVar``, so it is correct per
thread *and* per async task without any locking.

Spans are deliberately cheap records (name, trace_id, wall start, attrs,
monotonic start, duration), kept in a bounded ring buffer for inspection
(``recent()`` / the ``/v1/metrics.json`` view) and optionally mirrored as
structured JSON log lines keyed by trace-id (see :mod:`.logsink`). In a
process that has imported JAX an open span is also a
``jax.profiler.TraceAnnotation``: an event of the profiler's trace, on its
clock, beside the device's operations.

A record holds one clock for its interval: ``start_mono`` and ``duration_s``
are both ``time.perf_counter()``, so ``start_mono + duration_s`` is the span's
end and a reader that keeps its own ``perf_counter()`` marks (a benchmark's
round spans, say) can place a record inside them, or cut the ring by an
interval (:func:`between`). ``start`` stays wall time (``time.time()``): the
JSON-lines sink and the REST plane's reports are keyed on it.
"""

from __future__ import annotations

import contextlib
import contextvars
import re
import sys
import threading
import time
import uuid
from collections import deque

from .logsink import emit as _log_emit

#: the wire header carrying the trace id (client -> REST -> service -> store)
TRACE_HEADER = "X-SDA-Trace"

#: accepted wire shape for an incoming trace id — anything else is replaced
#: rather than stored/logged verbatim (header values end up in log lines)
_TRACE_RE = re.compile(r"[A-Za-z0-9_.:-]{1,64}")

_trace_var: contextvars.ContextVar = contextvars.ContextVar(
    "sda_trace_id", default=None
)

#: records the ring keeps. The busiest untraced 30 s window of the benchmark
#: (``c5-hostfed``: 46 rounds of about 45 records) leaves about 2 100 behind a
#: set-up's few hundred; what is overwritten unread is counted
#: (``sda_telemetry_spans_dropped_total``)
RING_RECORDS = 4096


def _profiler_annotation(name: str):
    """The open span as an event of the JAX profiler's trace, where JAX is
    already imported; a process that never imports it (the REST planes)
    still does not."""
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    if profiler is None:
        return contextlib.nullcontext()
    return profiler.TraceAnnotation(name)


def new_trace_id() -> str:
    return uuid.uuid4().hex


def current_trace_id():
    """The trace id bound to this context, or None."""
    return _trace_var.get()


def sanitize_trace_id(raw) -> str | None:
    """A safe trace id from an untrusted wire value, or None."""
    if not raw:
        return None
    raw = str(raw).strip()
    return raw if _TRACE_RE.fullmatch(raw) else None


@contextlib.contextmanager
def trace(trace_id: str | None = None):
    """Bind ``trace_id`` (fresh one if None) for the dynamic extent;
    yields the bound id."""
    token = _trace_var.set(trace_id or new_trace_id())
    try:
        yield _trace_var.get()
    finally:
        _trace_var.reset(token)


def set_trace_id(trace_id: str | None):
    """Imperatively bind a trace id (REST handler threads, where the
    request lifecycle doesn't nest as a ``with`` block)."""
    return _trace_var.set(trace_id)


def between(spans, since_mono: float | None = None, until_mono: float | None = None) -> list:
    """The records of ``spans`` that start in ``[since_mono, until_mono)`` on
    the monotonic clock (``None``: open on that side). Half-open, so the cuts
    of consecutive intervals share no record and miss none. A record with no
    ``start_mono`` (one banked before the field existed) is in no interval."""
    if since_mono is None and until_mono is None:
        return list(spans)
    lo = float("-inf") if since_mono is None else since_mono
    hi = float("inf") if until_mono is None else until_mono
    return [s for s in spans if s.get("start_mono") is not None and lo <= s["start_mono"] < hi]


class SpanLog:
    """Bounded ring of finished spans + the span() timing entry point."""

    def __init__(self, registry, maxlen: int = RING_RECORDS):
        self._registry = registry
        self._lock = threading.Lock()
        self._spans: deque = deque(maxlen=maxlen)
        self._dropped = registry.counter(
            "sda_telemetry_spans_dropped_total",
            "span records the ring overwrote (its oldest) to take a new one",
        )

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        """Time a block; record {name, trace_id, start, attrs, start_mono,
        duration_s}.

        Disabled telemetry short-circuits to a bare yield — no clock
        reads, no record, no log line, no profiler annotation."""
        if not self._registry.enabled:
            yield None
            return
        record = {
            "name": name,
            "trace_id": _trace_var.get(),
            "start": time.time(),
            "attrs": attrs or None,
        }
        with _profiler_annotation(name):
            t0 = record["start_mono"] = time.perf_counter()
            try:
                yield record
            finally:
                record["duration_s"] = time.perf_counter() - t0
                self._keep(record)

    def record(self, name: str, start_mono: float, duration_s: float, **attrs) -> None:
        """Keep a span that somebody else timed (JAX's own events): it took
        ``duration_s`` from ``start_mono`` on ``time.perf_counter()``."""
        if not self._registry.enabled:
            return
        self._keep({
            "name": name,
            "trace_id": _trace_var.get(),
            "start": time.time() - (time.perf_counter() - start_mono),
            "attrs": attrs or None,
            "start_mono": start_mono,
            "duration_s": duration_s,
        })

    def _keep(self, record: dict) -> None:
        with self._lock:
            if len(self._spans) == self._spans.maxlen:
                self._dropped.inc()
            self._spans.append(record)
        _log_emit("span", record)

    def recent(
        self,
        name: str | None = None,
        trace_id: str | None = None,
        since_mono: float | None = None,
        until_mono: float | None = None,
    ) -> list:
        """Finished spans, oldest first, optionally filtered by name
        prefix, exact trace id, and the interval they start in
        (:func:`between`)."""
        with self._lock:
            spans = list(self._spans)
        if name is not None:
            spans = [s for s in spans if s["name"].startswith(name)]
        if trace_id is not None:
            spans = [s for s in spans if s["trace_id"] == trace_id]
        return between(spans, since_mono, until_mono)

    def reset(self) -> None:
        with self._lock:
            self._spans.clear()
