"""Device seconds a round of the device epilogue and reveal: everything under
the program's scopes ``fabric.epilogue`` (the limb sums recombined mod p, the
values laid out as planes, the share matmul) and ``fabric.reveal`` (the
Lagrange combination of the survivors' clerk sums, the aggregate laid out as
its words). Busiest chip; from the join of the trace with the round's compiled
programs (``benchmark/scopes.py``), so nothing where the join failed, or where
the round took the host road and ran neither."""

from benchmark import scopes

name = "epilogue.device_s"
unit = "s"
layer = "device epilogue and reveal"
moves = "round_s"
reads_spans = ()
reads = ("scope:fabric.epilogue", "scope:fabric.reveal")


def reduce(spans, trace, cell):
    if trace is None:
        return None
    parts = [scopes.scope_seconds(cell.scopes, read.split(":", 1)[1]) for read in reads]
    return None if None in parts else sum(parts) or None
