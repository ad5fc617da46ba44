"""The rate at which a round's rows reached the fold: the bytes the feed
brings in a round (the chunk steps of a round times a chunk's bytes) over
``engine.fold_s``, the seconds from the feed's first put to
``block_until_ready`` on the accumulator. The link's rate as the round sees
it, the fold under it included. Median over the window's rounds."""

import statistics

name = "feed.gb_per_s"
unit = "GB/s"
layer = "host feed"
moves = "round_s"
reads_spans = ("dispatch", "fold")


def reduce(spans, trace, cell):
    start = {s.round: s.start for s in spans if s.name == "dispatch"}
    end = {s.round: s.end for s in spans if s.name == "fold"}
    per_round = [end[r] - start[r] for r in start if r in end]
    if not per_round:
        return None
    return cell.chunk_bytes * cell.steps_per_round / statistics.median(per_round) / 1e9
