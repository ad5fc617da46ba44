"""Device seconds of collective operations per round, on the chip with
most (the limb psum of the sharded sum-first step)."""

name = "collective.s"
unit = "s"
layer = "collectives"
moves = "round_s"
reads_spans = ()


def reduce(spans, trace, cell):
    if trace is None or not cell.rounds:
        return None
    total, _exposed = trace.collective_seconds()
    return total / cell.rounds if total else None
