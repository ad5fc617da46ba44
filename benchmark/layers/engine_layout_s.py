"""Device seconds a round under the program's scope ``fabric.values``: the
concatenate, cast and reshape of the chunk in the per-participant engine.
Busiest chip; from the join of the trace with the step's compiled text
(``benchmark/scopes.py``), so nothing where the join failed."""

from benchmark import scopes

name = "engine.layout_s"
unit = "s"
layer = "fabric engines"
moves = "round_s"
reads_spans = ()


def reduce(spans, trace, cell):
    return scopes.scope_seconds(cell.scopes, "fabric.values")
