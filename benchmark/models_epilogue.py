"""What the device epilogue and reveal of a sum-first round have to do at the
least, computed from their shapes: the bytes they must move. Kept with the
benchmark, beside :mod:`benchmark.models`, so that no PR that claims a gain
can change the yardstick. Imports nothing of the program.

Both programs are the vector unit's (a 61-bit multiply mod p is some hundred
32-bit operations), for which the peaks table has no published number: their
share is of the memory bound alone, as the ChaCha kernels' is.
"""

from __future__ import annotations

#: a limb sum, a clerk sum or an aggregate value as the programs hold it:
#: 64 bits, as one int64 or two uint32 words
VALUE_BYTES = 8


def epilogue_bytes(limbs: int, n_batches: int, values_per_batch: int, clerks: int) -> int:
    """Bytes the clerk sums' program must move: the accumulator's ``limbs``
    sums of every batch's ``values_per_batch`` values (k + t) read once, the
    ``clerks`` sums of every batch written once."""
    return VALUE_BYTES * n_batches * (limbs * values_per_batch + clerks)


def reveal_bytes(n_batches: int, survivors: int, dim: int) -> int:
    """Bytes the reveal must move: the ``survivors``' clerk sums read once,
    the ``dim`` values of the aggregate written once."""
    return VALUE_BYTES * (n_batches * survivors + dim)


def round_bytes(plan, limbs: int, survivors: int) -> int:
    """Both programs' bytes for one round's epilogue and one reveal, from the
    program's plan of the scheme (batches, k, t, n, dim)."""
    values = plan.input_size + plan.rand_size
    return epilogue_bytes(limbs, plan.n_batches, values, plan.share_count) + reveal_bytes(
        plan.n_batches, survivors, plan.dim
    )
