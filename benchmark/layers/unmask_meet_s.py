"""Device seconds a round under the program's scope ``fabric.unmask/meet``:
the meeting of the chips' partial mask sums in the recipient's sharded fold
(the gather of every chip's ``(dim,)`` partial and their sum mod p), on the
chip that spent most there. From the join of the trace with the compiled text
of the round's programs (``benchmark/scopes.py``): nothing where the join
failed, and nothing from a program that has no such scope (a one-chip fold,
the parent's)."""

name = "unmask.meet_s"
unit = "s"
layer = "recipient unmask"
moves = "round_s"
reads_spans = ()

SCOPE = "fabric.unmask/meet"
reads = (f"scope:{SCOPE}",)


def reduce(spans, trace, cell):
    if trace is None or cell.scopes is None:
        return None
    met = [chip["by_path"].get(SCOPE, 0.0) for chip in cell.scopes["chips"].values()]
    return max(met, default=0.0) or None
