"""Seconds of the round's ``unmask`` span in the masked round over a mesh: the
seeds fetched to the host as a recipient receives them, and the driver's
``unmask`` (``ChaChaMasker.combine`` over the round's mesh: the seeds put
sharded, the sharded folds of the re-expanded masks, the host waiting on each;
then ``.unmask``): what a recipient of a masked round waits beyond an unmasked
one's reveal. Median over the window's rounds. (The same reading as
``masked_layers/unmask_stage_s.py`` makes in ``c5-masked``; the ``benchmark``
PR that hooks them keeps one file and lists the cells.)"""

import statistics

name = "unmask.stage_s"
unit = "s"
layer = "recipient unmask"
moves = "round_s"
reads_spans = ("unmask",)


def reduce(spans, trace, cell):
    per_round = [s.seconds for s in spans if s.name == "unmask"]
    return statistics.median(per_round) if per_round else None
