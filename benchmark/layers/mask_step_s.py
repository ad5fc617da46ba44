"""Device seconds a round under the program's scope ``fabric.mask`` in the
chunk step, the whole of it (``seed``, ``expand``, ``add``): everything the
participants' side does because its rows are masked.
Busiest chip; from the join of the trace with the step's compiled text
(``benchmark/scopes.py``), so nothing where the join failed."""

from benchmark import scopes

name = "mask.step_s"
unit = "s"
layer = "mask stage"
moves = "round_s"
reads_spans = ()
reads = ("scope:fabric.mask",)


def reduce(spans, trace, cell):
    if trace is None:
        return None
    return scopes.scope_seconds(cell.scopes, "fabric.mask")
