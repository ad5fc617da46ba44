"""Host seconds a round in the program's span ``fabric.reveal.fetch``: the
device reveal's aggregate crossing to the host as its two uint32 words.
Median over the traced window's rounds, on the profiler's clock. Nothing
where the round took the host road."""

name = "reveal.fetch_s"
unit = "s"
layer = "device epilogue and reveal"
moves = "round_s"
reads_spans = ()
reads = ("host_span:fabric.reveal.fetch",)


def reduce(spans, trace, cell):
    if cell.host_spans is None:
        return None
    return cell.host_spans.get("fabric.reveal.fetch") or None
