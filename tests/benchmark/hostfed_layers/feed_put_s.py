"""Host seconds a round in the program's span ``fabric.feed.put``: the
``jax.device_put`` calls of the round's chunks (one span a call), as long as
the calls themselves take; the crossing they start goes on after them.
Summed over a round's puts, median over the traced window's rounds, on the
profiler's clock."""

name = "feed.put_s"
unit = "s"
layer = "host feed"
moves = "round_s"
reads_spans = ()


def reduce(spans, trace, cell):
    if cell.host_spans is None:
        return None
    return cell.host_spans.get("fabric.feed.put") or None
