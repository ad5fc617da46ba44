"""Seconds of set-up in which compiled programs were read from the persistent
compile cache: the sum of the ``jax.cache_load`` records (one a hit, by the
module's name) from the process's start to the window's; 0 where every program
was compiled. Taken before the window (``benchmark/ring.py``). Nothing where
the ring holds no record of set-up, or overwrote one."""

from benchmark import ring

name = "setup.load_s"
unit = "s"
layer = "set-up"
moves = "setup_s"
reads_spans = ()
reads = ("ring:jax.",)


def before_window(since):
    return ring.jax_records(since)


def reduce(spans, trace, cell):
    return ring.seconds(cell.before_window.get(name), "jax.cache_load")
