"""The part of the collectives' device time during which no other
operation ran on that chip."""

name = "collective.exposed_share"
unit = "%"
layer = "collectives"
moves = "round_s"
reads_spans = ()


def reduce(spans, trace, cell):
    if trace is None:
        return None
    total, exposed = trace.collective_seconds()
    return 100.0 * exposed / total if total else None
