"""Seconds from the dispatch of a round's first chunk step to
``block_until_ready``: the fabric engine's part of the round."""

import statistics

name = "engine.fold_s"
unit = "s"
layer = "fabric engines"
moves = "round_s"
reads_spans = ("dispatch", "fold")


def reduce(spans, trace, cell):
    start = {s.round: s.start for s in spans if s.name == "dispatch"}
    end = {s.round: s.end for s in spans if s.name == "fold"}
    per_round = [end[r] - start[r] for r in start if r in end]
    return statistics.median(per_round) if per_round else None
