"""The ChaCha compaction kernel's share of its roofline: the least time the
chip could take to move the kernel's bytes (the keystream's draws read once as
their high and low planes, and every seed's accepted draws and their count
written once, for the seeds one chip expands on both sides of a round:
``benchmark/models_chacha.py``) at the memory's peak (``benchmark/peaks.json``),
over the device time of the ``chacha_compact`` operations of every program, on
the chip with most. Its prefix sums and shifts are the vector unit's, for
which the table has no published peak: the share is of the memory bound, and
cannot pass 100 %. Nothing where the trace holds no such operation."""

from benchmark import models_chacha

name = "chacha_compact_roofline"
unit = "%"
layer = "kernels"
moves = "round_s"
reads_spans = ()

#: the kernel's name (``pallas_call(name=...)``), which its operations carry
KERNEL = "chacha_compact"
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
    least = cell.rounds * models_chacha.compact_kernel_bytes(seeds, dim, modulus) / cell.peaks[
        "hbm_bytes_per_s"
    ]
    cell.log(
        f"[benchmark] {KERNEL}: least {least / cell.rounds:.6f} s a round "
        f"({seeds} seeds a chip), device {took / cell.rounds:.6f} s"
    )
    return 100.0 * least / took
