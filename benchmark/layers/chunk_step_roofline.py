"""The chunk step's share of its roofline: the least time the chip could
take for the step's bytes or int8 operations (``benchmark/models.py``, peaks
from ``benchmark/peaks.json``), over the device time the step took."""

from benchmark import models

name = "chunk_step_roofline"
unit = "%"
layer = "kernels"
moves = "round_s"
cells = None


def reduce(spans, trace, cell):
    if trace is None or not cell.rounds:
        return None
    rows = cell.traffic.chunk // cell.chips
    least, binds = models.least_seconds(
        models.chunk_step_bytes(cell.chunk_bytes // cell.chips, cell.acc_bytes),
        models.chunk_step_int8_ops(cell.traffic.share_matmul_in_step, rows, cell.plan),
        cell.peaks,
    )
    step_seconds = trace.max_busy_seconds() / (cell.rounds * cell.steps_per_round)
    cell.log(
        f"[benchmark] chunk step: least {least * 1e3:.4f} ms ({binds} binds), "
        f"device {step_seconds * 1e3:.3f} ms"
    )
    return 100.0 * least / step_seconds
