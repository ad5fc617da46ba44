"""The device epilogue and reveal's share of their roofline: the least time
the chip could take to move their bytes (the accumulator read once, the clerk
sums written once, the survivors' clerk sums read once, the aggregate written
once: ``benchmark/models_epilogue.py``) at the memory's peak
(``benchmark/peaks.json``), over the device seconds a round under the scopes
``fabric.epilogue`` and ``fabric.reveal`` on the busiest chip (the
``epilogue.device_s`` layer file's reading). Both programs are bound by the
vector unit, which has no published peak: the share is of the memory bound,
and cannot pass 100 %. Nothing where either scope did not run."""

from benchmark import models_epilogue, scopes

name = "device_epilogue_roofline"
unit = "%"
layer = "device epilogue and reveal"
moves = "round_s"
reads_spans = ()
reads = ("scope:fabric.epilogue", "scope:fabric.reveal")


def reduce(spans, trace, cell):
    if trace is None or cell.peaks is None:
        return None
    parts = [scopes.scope_seconds(cell.scopes, read.split(":", 1)[1]) for read in reads]
    if None in parts or not sum(parts):
        return None
    took = sum(parts)
    limbs = 1 if cell.plan.modulus <= 1 << 31 else 2
    survivors = cell.config["guarantees"]["reconstruction_threshold"]
    moved = models_epilogue.round_bytes(cell.plan, limbs, survivors)
    least = moved / cell.peaks["hbm_bytes_per_s"]
    cell.log(
        f"[benchmark] device epilogue and reveal: least {least:.6f} s a round "
        f"({moved} B), device {took:.6f} s"
    )
    return 100.0 * least / took
