"""Seconds a round keeps the device busy: the union of the intervals in
which an operation ran, on the busiest chip, per round of the traced window."""

name = "kernel.busy_s"
unit = "s"
layer = "kernels"
moves = "round_s"
reads_spans = ()


def reduce(spans, trace, cell):
    if trace is None or not cell.rounds:
        return None
    return trace.max_busy_seconds() / cell.rounds
