"""What the ChaCha mask expansion has to do at the least, computed from its
shapes: the blocks a seed's mask needs and the bytes the rounds kernel must
move for them. Kept with the benchmark, beside :mod:`benchmark.models`, so
that no PR that claims a gain can change the yardstick. Imports nothing of
the program: it counts what the algorithm needs, not the margin the program
generates on top (its window is some standard deviations wider).
"""

from __future__ import annotations

#: a ChaCha block is sixteen uint32 words: read as a state, written as keystream
BLOCK_BYTES_IN_AND_OUT = 2 * 16 * 4


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
    block's state in, its keystream out."""
    return seeds * blocks_per_seed(dim, modulus) * BLOCK_BYTES_IN_AND_OUT


def seeds_expanded_per_round(participants: int, sides: int = 2) -> int:
    """Every participant's seed is expanded once on each side of a round:
    by the mask stage, and by the recipient."""
    return participants * sides
