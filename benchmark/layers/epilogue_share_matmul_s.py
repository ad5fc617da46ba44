"""Host seconds a round in the program's span ``fabric.epilogue.share_matmul``:
the participant sum times the share matrix mod p, once a round, in the
sum-first epilogue.
Median over the traced window's rounds, on the profiler's clock."""

name = "epilogue.share_matmul_s"
unit = "s"
layer = "host epilogue and reconstruct"
moves = "round_s"
reads_spans = ()
reads = ("host_span:fabric.epilogue.share_matmul",)


def reduce(spans, trace, cell):
    if cell.host_spans is None:
        return None
    return cell.host_spans.get("fabric.epilogue.share_matmul") or None
