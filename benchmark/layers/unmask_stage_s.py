"""Seconds of the round's ``unmask`` span: the seeds fetched as a recipient
receives them, then the driver's (or the round's) ``ChaChaMasker.combine`` (the
device folds of the re-expanded masks, on one chip or sharded over the round's
mesh) and ``.unmask``: what a recipient of a masked round waits beyond an
unmasked one's reveal. Median over the window's rounds. (``unmask.s`` and
``unmask_s.py`` are the toy round's, which tier-1 drops into a copy of
``benchmark/layers``.)"""

import statistics

name = "unmask.stage_s"
unit = "s"
layer = "recipient unmask"
moves = "round_s"
reads_spans = ("unmask",)


def reduce(spans, trace, cell):
    per_round = [s.seconds for s in spans if s.name == "unmask"]
    return statistics.median(per_round) if per_round else None
