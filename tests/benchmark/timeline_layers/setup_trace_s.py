"""Seconds of set-up in which JAX was tracing a function of the program to a
jaxpr: the union of the ``jax.trace`` records (``sda_tpu/telemetry/jaxevents.py``;
one a traced function of a millisecond or more, by name) that start before the
window's first ``round`` span. A union, not a sum: a function traced while
another is being traced lies inside the outer one's interval. The program's span
ring is reached by dotted path; both clocks are ``time.perf_counter()``.
Nothing where the ring holds no ``jax.*`` record from before the window (a
program without the listener, or one whose programs the process had compiled
before)."""

from benchmark import traffic

name = "setup.trace_s"
unit = "s"
layer = "set-up"
moves = "setup_s"
reads_spans = ("round",)

RING = "sda_tpu.telemetry.snapshot"


def reduce(spans, trace, cell):
    first = min((s.start for s in spans if s.name == "round"), default=None)
    ring = traffic.resolve(RING)(1 << 20).get("spans", ())
    before = [
        r for r in ring
        if r["name"].startswith("jax.") and r.get("start_mono") is not None
        and first is not None and r["start_mono"] < first
    ]
    if not before:
        return None
    took = [
        (r["start_mono"], r["start_mono"] + r["duration_s"])
        for r in before if r["name"] == "jax.trace"
    ]
    return traffic.resolve("sda_tpu.telemetry.flight._union_coverage")(took)
