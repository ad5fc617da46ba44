"""The device seconds under the program's scope ``fabric.mask`` (the mask
stage of the chunk step: ``seed``, ``expand``, ``add``) on the chip that spent
most there, over those on the chip that spent least: 1 where every chip masks
its share of the rows in the same time, more where one lags and the others
wait for it in the step's psum. From the join of the trace with the compiled
text of the round's programs (``benchmark/scopes.py``): nothing where the join
failed, on one chip, or where a chip masked nothing."""

name = "mask.chip_skew"
unit = "ratio"
layer = "mask stage"
moves = "round_s"
reads_spans = ()

SCOPE = "fabric.mask"


def reduce(spans, trace, cell):
    if trace is None or cell.scopes is None or len(cell.scopes["chips"]) < 2:
        return None
    masked = [chip["by_scope"].get(SCOPE, 0.0) for chip in cell.scopes["chips"].values()]
    return max(masked) / min(masked) if min(masked) > 0 else None
