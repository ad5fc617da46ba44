"""Lightweight spans with a propagated trace-id.

A *trace-id* is an opaque hex token that follows one logical operation
across layers: the client stamps it on every HTTP request
(``X-SDA-Trace``), the REST server adopts it for the handler thread, and
every ``span()`` recorded below — service, stores, crypto — carries it.
Propagation rides a ``contextvars.ContextVar``, so it is correct per
thread *and* per async task without any locking.

Spans are deliberately cheap records (name, trace_id, wall start,
duration, attrs), kept in a bounded ring buffer for inspection
(``recent()`` / the ``/v1/metrics.json`` view) and optionally mirrored as
structured JSON log lines keyed by trace-id (see :mod:`.logsink`). In a
process that has imported JAX an open span is also a
``jax.profiler.TraceAnnotation``: an event of the profiler's trace, on its
clock, beside the device's operations.
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

#: the wire header carrying the trace id (client -> REST -> service -> store)
TRACE_HEADER = "X-SDA-Trace"

#: accepted wire shape for an incoming trace id — anything else is replaced
#: rather than stored/logged verbatim (header values end up in log lines)
_TRACE_RE = re.compile(r"[A-Za-z0-9_.:-]{1,64}")

_trace_var: contextvars.ContextVar = contextvars.ContextVar(
    "sda_trace_id", default=None
)


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


class SpanLog:
    """Bounded ring of finished spans + the span() timing entry point."""

    def __init__(self, registry, maxlen: int = 4096):
        self._registry = registry
        self._lock = threading.Lock()
        self._spans: deque = deque(maxlen=maxlen)

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        """Time a block; record {name, trace_id, start, duration_s, attrs}.

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
            t0 = time.perf_counter()
            try:
                yield record
            finally:
                record["duration_s"] = time.perf_counter() - t0
                with self._lock:
                    self._spans.append(record)
                from .logsink import emit as _log_emit

                _log_emit("span", record)

    def recent(self, name: str | None = None, trace_id: str | None = None) -> list:
        """Finished spans, oldest first, optionally filtered by name
        prefix and/or exact trace id."""
        with self._lock:
            spans = list(self._spans)
        if name is not None:
            spans = [s for s in spans if s["name"].startswith(name)]
        if trace_id is not None:
            spans = [s for s in spans if s["trace_id"] == trace_id]
        return spans

    def reset(self) -> None:
        with self._lock:
            self._spans.clear()
