"""Host seconds a round in the program's span ``fabric.reconstruct``: Lagrange
from the surviving clerks' sums.
Median over the traced window's rounds, on the profiler's clock."""

name = "epilogue.reconstruct_s"
unit = "s"
layer = "host epilogue and reconstruct"
moves = "round_s"
reads_spans = ()


def reduce(spans, trace, cell):
    if cell.host_spans is None:
        return None
    return cell.host_spans.get("fabric.reconstruct") or None
