"""Seconds of the round's ``unmask`` span in the masked host-fed round: the
seeds fetched as a recipient receives them, the driver's ``unmask``
(``ChaChaMasker.combine``: the device folds of the re-expanded masks, the host
waiting on each; then ``.unmask``): what a recipient of a masked round waits
beyond an unmasked one's reveal. Median over the window's rounds. (The same
reading as ``masked_layers/unmask_stage_s.py`` makes in ``c5-masked``; the
``benchmark`` PR that hooks them keeps one file and lists both cells.)"""

import statistics

name = "unmask.stage_s"
unit = "s"
layer = "recipient unmask"
moves = "round_s"
reads_spans = ("unmask",)


def reduce(spans, trace, cell):
    per_round = [s.seconds for s in spans if s.name == "unmask"]
    return statistics.median(per_round) if per_round else None
