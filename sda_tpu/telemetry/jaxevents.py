"""JAX's own trace / lower / compile / cache-load events, counted in the
registry and kept in the span ring by program name.

JAX reports what it does to a program before the first dispatch through
``jax.monitoring``: a duration event when it has traced a function to a jaxpr,
lowered a jaxpr to an MLIR module, and compiled the module or loaded it from
the persistent compile cache. :func:`register` (called once a process, from
``ops.jaxcfg.ensure_x64``) puts one listener on the duration events and one on
the plain events; from then on

========================================================  ============  ===============
``jax.monitoring`` event                                  ``event``     ring record
========================================================  ============  ===============
``/jax/core/compile/jaxpr_trace_duration``                ``trace``     ``jax.trace`` (1 ms or more)
``/jax/core/compile/jaxpr_to_mlir_module_duration``       ``lower``     ``jax.lower``
``/jax/core/compile/backend_compile_duration``, no hit    ``compile``   ``jax.compile``
``/jax/compilation_cache/cache_retrieval_time_sec``       ``cache_load``  ``jax.cache_load``
========================================================  ============  ===============

each ticks ``sda_jax_events_total{event}`` and adds its seconds to
``sda_jax_event_seconds_total{event}`` (``event`` is this closed set: a
program's name is an attribute of a record, never a label), and leaves a
record with attr ``program`` whose ``start_mono`` is the listener's
``perf_counter()`` less the event's duration. ``program`` is JAX's
``fun_name`` as the compiled module is called (``jit(step)`` becomes
``jit_step``, the name in the compiler's log and in a profiler's trace); a
traced function's is its own (``step``).

``backend_compile_duration`` wraps the read of the persistent cache too. A hit
is told on the same thread just before it (``/jax/compilation_cache/cache_hits``
and, with its seconds, ``cache_retrieval_time_sec``): it becomes one
``jax.cache_load`` of the retrieval's seconds, named by the compile event that
follows, and no ``jax.compile``. A compile whose executable was then written to
the cache (the plain event ``/jax/compilation_cache/cache_misses``) says
``cache="miss"``.

A set-up traces thousands of small jaxprs (6 500 in the benchmark's masked
cell), nested ones inside their outer one's interval: every one is counted, so
the counter's seconds count a nested trace in its outer one's too (an upper
bound), and only those of :data:`TRACE_RECORD_FLOOR_S` or more are kept, so
that the ring still holds the set-up when the window is over. Nothing fires per
dispatch: a program that is compiled calls neither listener. What JAX did
before :func:`register` is not the program's and is not counted.

With telemetry off each listener is a branch and a return.
"""

from __future__ import annotations

import re
import threading
import time

from . import _REGISTRY, _SPANS

#: a traced function is kept as a ``jax.trace`` record from this many seconds
TRACE_RECORD_FLOOR_S = 1e-3

_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval",
}
_CACHE_MISS = "/jax/compilation_cache/cache_misses"
# the counters' handles, held (a set-up calls the listener thousands of times)
_COUNTED = {
    event: (
        _REGISTRY.counter(
            "sda_jax_events_total", "programs JAX traced, lowered, compiled or loaded", event=event
        ),
        _REGISTRY.counter(
            "sda_jax_event_seconds_total",
            "seconds JAX spent tracing, lowering, compiling or loading programs",
            event=event,
        ),
    )
    for event in ("trace", "lower", "compile", "cache_load")
}
# what MLIR leaves of a name when it calls a module by it
_NOT_IN_A_MODULE_NAME = re.compile(r"[^\w.-]")

# what the cache said of the module this thread is compiling, until the
# compile event that names the module: after a hit ``loaded`` = (the
# retrieval's seconds, perf_counter() at its end), after a miss ``missed``
_pending = threading.local()
_registered = False


def _count(event: str, seconds: float) -> None:
    events, took = _COUNTED[event]
    events.inc()
    took.inc(seconds)


def _keep(name: str, start_mono: float, seconds: float, fun_name, **attrs) -> None:
    program = _NOT_IN_A_MODULE_NAME.sub("_", str(fun_name)).rstrip("_")
    _SPANS.record(name, start_mono, seconds, program=program, **attrs)


def _on_duration(event: str, seconds: float, fun_name=None, **_kwargs) -> None:
    if not _REGISTRY.enabled:
        return
    kind = _DURATIONS.get(event)
    if kind is None:
        return
    now = time.perf_counter()
    if kind == "trace":
        _count("trace", seconds)
        if seconds >= TRACE_RECORD_FLOOR_S:
            _keep("jax.trace", now - seconds, seconds, fun_name)
    elif kind == "lower":
        _count("lower", seconds)
        _keep("jax.lower", now - seconds, seconds, fun_name)
    elif kind == "retrieval":
        _pending.loaded = (seconds, now)
    else:
        loaded = getattr(_pending, "loaded", None)
        missed = getattr(_pending, "missed", False)
        _pending.loaded, _pending.missed = None, False
        if loaded is not None:
            seconds, end = loaded
            _count("cache_load", seconds)
            _keep("jax.cache_load", end - seconds, seconds, fun_name)
        else:
            _count("compile", seconds)
            attrs = {"cache": "miss"} if missed else {}
            _keep("jax.compile", now - seconds, seconds, fun_name, **attrs)


def _on_event(event: str, **_kwargs) -> None:
    if not _REGISTRY.enabled:
        return
    if event == _CACHE_MISS:
        _pending.missed = True


def register() -> None:
    """Put the two listeners on ``jax.monitoring``, once a process."""
    global _registered
    if _registered:
        return
    _registered = True
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
