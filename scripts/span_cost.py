"""What one ``telemetry.span`` costs on the host, in nanoseconds: in a process
that has not imported JAX, in one that has (an open span is then also a
``jax.profiler.TraceAnnotation``), in one that is taking a profile, and with
``SDA_TELEMETRY=0``; beside each, the annotation alone, the part that the
profiler's clock costs. One JSON line. ``sda_tpu`` is taken from
``PYTHONPATH``, so that the same file measures another checkout (one from
before the annotation reports the span alone):

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
    print(json.dumps({"ns": out, "jax": jax.__version__}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
