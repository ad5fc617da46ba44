"""Seconds of set-up in which JAX was tracing a function of the program to a
jaxpr: the union of the ``jax.trace`` records (one a traced function of a
millisecond or more, by name) from the process's start to the window's. A
union, not a sum: a function traced while another is being traced lies inside
the outer one's interval. Taken before the window (``benchmark/ring.py``).
Nothing where the ring holds no record of set-up, or overwrote one."""

from benchmark import ring, trace_reduce

name = "setup.trace_s"
unit = "s"
layer = "set-up"
moves = "setup_s"
reads_spans = ()
reads = ("ring:jax.",)


def before_window(since):
    return ring.jax_records(since)


def reduce(spans, trace, cell):
    records = cell.before_window.get(name)
    if records is None:
        return None
    traced = [
        (r["start_mono"], r["start_mono"] + r["duration_s"])
        for r in records if r["name"] == "jax.trace"
    ]
    return sum(end - start for start, end in trace_reduce.merge(traced))
