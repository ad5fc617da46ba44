"""Device seconds a round under the program's scope ``fabric.share_matmul``: the
7-bit limb split and the int8 ``dot_general``s of the per-participant engine.
Busiest chip; from the join of the trace with the step's compiled text
(``benchmark/scopes.py``), so nothing where the join failed."""

from benchmark import scopes

name = "engine.share_matmul_s"
unit = "s"
layer = "fabric engines"
moves = "round_s"
reads_spans = ()
reads = ("scope:fabric.share_matmul",)


def reduce(spans, trace, cell):
    return scopes.scope_seconds(cell.scopes, "fabric.share_matmul")
