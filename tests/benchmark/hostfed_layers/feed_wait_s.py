"""Host seconds a round in the program's span ``fabric.feed.wait``: the feed
blocked on one of its two bounds, the caller's on blocks alive (the oldest
alive block's last chunk step) or the link's on bytes crossing (the oldest
crossing chunk's landing), before it may put the next chunk.
Summed over a round's waits, median over the traced window's rounds, on the
profiler's clock; nothing where no round waited (bounds the round never
reaches)."""

name = "feed.wait_s"
unit = "s"
layer = "host feed"
moves = "round_s"
reads_spans = ()


def reduce(spans, trace, cell):
    if cell.host_spans is None:
        return None
    return cell.host_spans.get("fabric.feed.wait") or None
