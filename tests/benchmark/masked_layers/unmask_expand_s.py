"""Device seconds a round under the program's scope ``fabric.unmask/expand``:
the recipient's re-expansion of every seed in its fold program
(``ops.chacha_pallas.fold_chunk_jit``), the sum of the masks left out.
Busiest chip; from the join of the trace with the compiled text of the
round's programs (``benchmark/scopes.py``), so nothing where the join failed."""

name = "unmask.expand_s"
unit = "s"
layer = "recipient unmask"
moves = "round_s"
reads_spans = ()


def reduce(spans, trace, cell):
    if trace is None or cell.scopes is None:
        return None
    chip = cell.scopes["chips"][cell.scopes["busiest_chip"]]
    return chip["by_path"].get("fabric.unmask/expand")
