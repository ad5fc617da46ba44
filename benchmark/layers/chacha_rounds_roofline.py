"""The ChaCha rounds kernel's share of its roofline: the least time the chip
could take to move the kernel's bytes (every seed read and every block's
keystream written, for the seeds one chip expands on both sides of a round:
``benchmark/models_chacha.py``) at the memory's peak (``benchmark/peaks.json``),
over the device time of the ``chacha_rounds`` operations of every program, on
the chip with most. The kernel is bound by the vector unit (twenty rounds of
adds, xors and rotates a block), for which the table has no published peak:
the share is of the memory bound, and cannot pass 100 %. Nothing where the
trace holds no such operation."""

from benchmark import models_chacha

name = "chacha_rounds_roofline"
unit = "%"
layer = "kernels"
moves = "round_s"
reads_spans = ()

#: the kernel's name (``pallas_call(name=...)``), which its operations carry
KERNEL = "chacha_rounds"
reads = (f"kernel:{KERNEL}",)


def reduce(spans, trace, cell):
    if trace is None or not cell.rounds:
        return None
    took = trace.max_kernel_seconds(KERNEL)
    if not took:
        return None
    participants = cell.traffic.rows * cell.traffic.passes
    seeds = models_chacha.seeds_expanded_per_round(participants) // cell.chips
    dim, modulus = cell.config["dim"], cell.plan.modulus
    least = cell.rounds * models_chacha.rounds_kernel_bytes(seeds, dim, modulus) / cell.peaks[
        "hbm_bytes_per_s"
    ]
    cell.log(
        f"[benchmark] {KERNEL}: least {least / cell.rounds:.6f} s a round "
        f"({seeds} seeds a chip x {models_chacha.blocks_per_seed(dim, modulus)} blocks), "
        f"device {took / cell.rounds:.6f} s"
    )
    return 100.0 * least / took
