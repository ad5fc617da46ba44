"""Host seconds a round in the program's span ``fabric.epilogue.recombine``: the
exact value sums and ``% p`` of the sum-first epilogue, or the whole of
``limb_recombine_host``.
Median over the traced window's rounds, on the profiler's clock."""

name = "epilogue.recombine_s"
unit = "s"
layer = "host epilogue and reconstruct"
moves = "round_s"
reads_spans = ()


def reduce(spans, trace, cell):
    if cell.host_spans is None:
        return None
    return cell.host_spans.get("fabric.epilogue.recombine") or None
