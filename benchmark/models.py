"""What one chunk step has to do at the least: the bytes it must move and
the int8 operations it must make, computed from its shapes. Kept with the
benchmark so that no PR that claims a gain can change the yardstick.
(Copied in spirit from ``bench.py``'s roofline block, which counts what the
program happens to move; this counts what the algorithm needs.)
"""

from __future__ import annotations


def chunk_step_bytes(chunk_bytes_per_chip: int, acc_bytes: int) -> int:
    """Bytes one chunk step must move on a chip: every input value read
    once, the accumulator read and written. The share randomness is drawn
    on the chip and need never touch memory."""
    return chunk_bytes_per_chip + 2 * acc_bytes


def limb_count(modulus: int) -> int:
    """7-bit limbs of a value below ``modulus``, as the int8 share matmul
    splits them."""
    return -(-int(modulus).bit_length() // 7)


def chunk_step_int8_ops(share_matmul_in_step: bool, rows_per_chip: int, plan) -> int:
    """int8 multiply-adds, counted as two operations each, that one chunk
    step needs on a chip. A step that shares every participant multiplies
    each one's (B, k+t) value rows by the (k+t, n) share matrix in limb
    space: L x L limb products. A sum-first step makes no matrix product
    (its share matmul runs once, on the host, on the participant sum); the
    traffic file says which the step is."""
    if not share_matmul_in_step:
        return 0
    limbs = limb_count(plan.modulus)
    k_plus_t = plan.input_size + plan.rand_size
    macs = rows_per_chip * plan.n_batches * k_plus_t * plan.share_count * limbs * limbs
    return 2 * macs


def least_seconds(bytes_moved: int, int8_ops: int, peaks: dict) -> tuple:
    """``(seconds, which)``: the least time the chip could take, and the
    peak that binds (``hbm`` or ``int8``)."""
    by_bytes = bytes_moved / peaks["hbm_bytes_per_s"]
    by_ops = int8_ops / peaks["int8_ops_per_s"]
    return (by_bytes, "hbm") if by_bytes >= by_ops else (by_ops, "int8")
