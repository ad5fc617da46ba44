"""Seconds of the host epilogue and reconstruct: limb accumulator to clerk
sums, drop the configuration's clerks, Lagrange from the survivors."""

import statistics

name = "epilogue.s"
unit = "s"
layer = "host epilogue and reconstruct"
moves = "round_s"
reads_spans = ("epilogue",)


def reduce(spans, trace, cell):
    per_round = [s.seconds for s in spans if s.name == "epilogue"]
    return statistics.median(per_round) if per_round else None
