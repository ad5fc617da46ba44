"""Seconds of set-up in which JAX lowered the program's jaxprs to MLIR modules:
the sum of the ``jax.lower`` records (one a module, by name) from the process's
start to the window's. Taken before the window (``benchmark/ring.py``). Nothing
where the ring holds no record of set-up, or overwrote one."""

from benchmark import ring

name = "setup.lower_s"
unit = "s"
layer = "set-up"
moves = "setup_s"
reads_spans = ()
reads = ("ring:jax.",)


def before_window(since):
    return ring.jax_records(since)


def reduce(spans, trace, cell):
    return ring.seconds(cell.before_window.get(name), "jax.lower")
