"""The part of the collectives' device time during which no other
operation ran on that chip."""

name = "collective.exposed_share"
unit = "%"
layer = "collectives"
moves = "round_s"
cells = ["c5-sumfirst-x4"]


def reduce(spans, trace, cell):
    if trace is None:
        return None
    total, exposed = trace.collective_seconds()
    return 100.0 * exposed / total if total else None
