"""The toy round's own layer metric, as the file a later PR would add beside
its round: seconds of the stage that only this round has, the masks summed on
the device and taken off what the clerks revealed. The tests drop it into a
temporary copy of the benchmark as ``benchmark/layers/unmask_s.py``."""

import statistics

name = "unmask.s"
unit = "s"
layer = "recipient unmask"
moves = "round_s"
reads_spans = ("unmask",)


def reduce(spans, trace, cell):
    per_round = [s.seconds for s in spans if s.name == "unmask"]
    return statistics.median(per_round) if per_round else None
