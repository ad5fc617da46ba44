"""What one ``telemetry.span`` costs on the host, in nanoseconds: in a process
that has not imported JAX, in one that has (an open span is then also a
``jax.profiler.TraceAnnotation``), in one that is taking a profile, and with
``SDA_TELEMETRY=0``; beside each, the annotation alone, the part that the
profiler's clock costs. Then what the timeline costs: one call of the listener
on JAX's events (a traced jaxpr under the record's floor, the six thousand a
set-up; one that is kept; with telemetry off), and ``telemetry.snapshot`` of a
full ring, what a reader pays once after the window. One JSON line. ``sda_tpu``
is taken from ``PYTHONPATH``, so that the same file measures another checkout
(one from before the annotation reports the span alone, one from before the
listener no listener):

    PYTHONPATH=. python scripts/span_cost.py
    PYTHONPATH=.archive_tree/parent python scripts/span_cost.py
"""

import importlib
import json
import sys
import tempfile
import timeit

from sda_tpu import telemetry

spans = importlib.import_module("sda_tpu.telemetry.spans")  # the package's `spans` is a function

NUMBER, REPEAT = 5000, 15


def least_ns(fn) -> float:
    return min(timeit.repeat(fn, number=NUMBER, repeat=REPEAT)) / NUMBER * 1e9


def one_span():
    with telemetry.span("fabric.epilogue.recombine", modulus_bits=61, shape=(2, 20000, 7)):
        pass


def one_annotation():
    with spans._profiler_annotation("fabric.epilogue.recombine"):
        pass


def listener_and_snapshot() -> dict:
    """Run last: it fills the ring."""
    out = {}
    ring = getattr(telemetry, "RING_RECORDS", 4096)
    for _ in range(ring):
        one_span()
    took = timeit.repeat(lambda: telemetry.snapshot(ring), number=5, repeat=5)
    out["snapshot_full_ring"] = min(took) / 5 * 1e9
    try:
        events = importlib.import_module("sda_tpu.telemetry.jaxevents")
    except ImportError:
        return out
    trace = "/jax/core/compile/jaxpr_trace_duration"
    out["listener_counted"] = least_ns(lambda: events._on_duration(trace, 1e-5, fun_name="f"))
    out["listener_kept"] = least_ns(lambda: events._on_duration(trace, 1e-2, fun_name="f"))
    telemetry.set_enabled(False)
    out["listener_telemetry_off"] = least_ns(lambda: events._on_duration(trace, 1e-2, fun_name="f"))
    telemetry.set_enabled(True)
    return out


def measure() -> dict:
    out = {"span": least_ns(one_span)}
    if hasattr(spans, "_profiler_annotation"):
        out["annotation_alone"] = least_ns(one_annotation)
    return out


def main() -> int:
    out = {"no_jax": measure()}
    assert "jax" not in sys.modules
    import jax

    out["jax_imported"] = measure()
    with tempfile.TemporaryDirectory() as tmp:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # as the benchmark's harness takes its profile
        options.host_tracer_level = 2
        jax.profiler.start_trace(tmp, profiler_options=options)
        try:
            out["profile_being_taken"] = measure()
        finally:
            jax.profiler.stop_trace()
    telemetry.set_enabled(False)
    out["telemetry_off"] = {"span": least_ns(one_span)}
    telemetry.set_enabled(True)
    out["timeline"] = listener_and_snapshot()
    print(json.dumps({"ns": out, "jax": jax.__version__}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
