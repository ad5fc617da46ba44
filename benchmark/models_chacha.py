"""What the ChaCha mask expansion has to do at the least, computed from its
shapes: the blocks a seed's mask needs and the bytes each of its two kernels
must move for them. Kept with the benchmark, beside :mod:`benchmark.models`, so
that no PR that claims a gain can change the yardstick. Imports nothing of
the program: it counts what the algorithm needs, not the margin the program
generates on top (its window is some standard deviations wider).
"""

from __future__ import annotations

#: a ChaCha block's keystream: sixteen uint32 words, eight u64 draws. The
#: rounds kernel makes every block's state in registers from the seed, so the
#: keystream written is all a block moves
BLOCK_BYTES = 16 * 4

#: a seed as the kernels read it: four uint32 words (128 bits)
SEED_BYTES = 4 * 4

#: an accepted draw as the compaction writes it: its high and low uint32 words
ACCEPTED_BYTES = 2 * 4

#: a row's count of accepted draws, as the compaction writes it: one int32
COUNT_BYTES = 4


def rejected_share(modulus: int) -> float:
    """The share of u64 draws that rand 0.3's ``gen_range(0, modulus)``
    rejects: those at or above ``u64::MAX - u64::MAX % modulus``."""
    u64_max = (1 << 64) - 1
    return (u64_max % int(modulus) + 1) / float(1 << 64)


def blocks_per_seed(dim: int, modulus: int) -> int:
    """ChaCha blocks whose eight u64 draws hold, in expectation, the ``dim``
    accepted draws of one seed's mask."""
    draws = dim / (1.0 - rejected_share(modulus))
    return -(-int(draws + 0.5) // 8)


def rounds_kernel_bytes(seeds: int, dim: int, modulus: int) -> int:
    """Bytes the rounds kernel must move to expand ``seeds`` seeds: every
    seed read, every block's keystream written."""
    return seeds * (SEED_BYTES + blocks_per_seed(dim, modulus) * BLOCK_BYTES)


def compact_kernel_bytes(seeds: int, dim: int, modulus: int) -> int:
    """Bytes the compaction kernel must move for ``seeds`` seeds: the
    keystream's draws read once, as its high and low planes, and for every
    seed its ``dim`` accepted draws and their count written once."""
    per_seed = blocks_per_seed(dim, modulus) * BLOCK_BYTES + dim * ACCEPTED_BYTES + COUNT_BYTES
    return seeds * per_seed


def seeds_expanded_per_round(participants: int, sides: int = 2) -> int:
    """Every participant's seed is expanded once on each side of a round:
    by the mask stage, and by the recipient."""
    return participants * sides
