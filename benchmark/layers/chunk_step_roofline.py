"""The chunk step's share of its roofline: the least time the chip could
take for the step's bytes or int8 operations (``benchmark/models.py``, peaks
from ``benchmark/peaks.json``), over the device time the step took: the busy
seconds of the operations of the chunk step's own program (the first of the
round's ``steps``) on the chip with most, not of whatever else the round runs
on the device."""

from benchmark import models

name = "chunk_step_roofline"
unit = "%"
layer = "kernels"
moves = "round_s"
reads_spans = ()


def reduce(spans, trace, cell):
    if trace is None or not cell.rounds or not cell.chunk_step_modules:
        return None
    busy = trace.max_busy_seconds(cell.chunk_step_modules)
    if not busy:
        return None
    rows = cell.traffic.chunk // cell.chips
    least, binds = models.least_seconds(
        models.chunk_step_bytes(cell.chunk_bytes // cell.chips, cell.acc_bytes),
        models.chunk_step_int8_ops(cell.traffic.share_matmul_in_step, rows, cell.plan),
        cell.peaks,
    )
    step_seconds = busy / (cell.rounds * cell.steps_per_round)
    cell.log(
        f"[benchmark] chunk step: least {least * 1e3:.4f} ms ({binds} binds), "
        f"device {step_seconds * 1e3:.3f} ms ({busy:.6f} s of the window's "
        f"{trace.max_busy_seconds():.6f} busy are {sorted(cell.chunk_step_modules)}'s)"
    )
    return 100.0 * least / step_seconds
