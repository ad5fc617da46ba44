"""Device seconds a round under the program's scope ``fabric.input``: the pad and
reshape of the chunk and the exact limb sums of the secrets. The sum-first
cells only: in the per-participant engine the compiler fuses it away.
Busiest chip; from the join of the trace with the step's compiled text
(``benchmark/scopes.py``), so nothing where the join failed."""

from benchmark import scopes

name = "engine.input_s"
unit = "s"
layer = "fabric engines"
moves = "round_s"
reads_spans = ()
reads = ("scope:fabric.input",)


def reduce(spans, trace, cell):
    return scopes.scope_seconds(cell.scopes, "fabric.input")
