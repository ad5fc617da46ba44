"""Device seconds a round that carry none of the program's scopes: loops the
compiler builds with no metadata, the harness's own ``acc + out``, and the
operations of other modules than the step's.
Busiest chip; from the join of the trace with the step's compiled text
(``benchmark/scopes.py``), so nothing where the join failed."""

from benchmark import scopes

name = "engine.unscoped_s"
unit = "s"
layer = "fabric engines"
moves = "round_s"
reads_spans = ()


def reduce(spans, trace, cell):
    return scopes.scope_seconds(cell.scopes, scopes.UNSCOPED)
