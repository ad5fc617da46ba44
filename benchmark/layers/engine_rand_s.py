"""Device seconds a round under the program's scope ``fabric.rand``, the whole of
it (``draw`` and ``limb_sum``): everything that exists because of share
randomness. Since PR 26 the draw is fused into the reductions that consume it,
so ``draw`` alone reads microseconds and would bound nothing.
Busiest chip; from the join of the trace with the step's compiled text
(``benchmark/scopes.py``), so nothing where the join failed."""

from benchmark import scopes

name = "engine.rand_s"
unit = "s"
layer = "fabric engines"
moves = "round_s"
reads_spans = ()
reads = ("scope:fabric.rand",)


def reduce(spans, trace, cell):
    return scopes.scope_seconds(cell.scopes, "fabric.rand")
