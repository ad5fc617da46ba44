"""Faults of the masked round, handed to the harness through a traffic
file's dotted names (as ``faulty.py``'s are): the benchmark must call a run
with any of them incorrect."""

from __future__ import annotations


def unmasking_chunk_engine(masked_entry, entry, plan, masking):
    """A masked chunk engine that skips the mask stage: the seeds and counts
    are the real step's, and the rows go to the entry as they are, so the
    clerks' sums carry no mask."""
    import jax

    real = masked_entry(entry, plan, masking)

    def fn(secrets, key):
        _acc, seeds, counts = real(secrets, key)
        return entry(secrets, jax.random.split(key)[0], plan), seeds, counts

    return fn


def off_by_one_fold():
    """The recipient's fold handle, its partial mask sum off by one in one
    element: what the aggregate's comparison would also catch in the window,
    and the warm-up's comparison of the mask parts catches by itself."""
    from sda_tpu.ops.chacha_pallas import fold_chunk_jit

    fold = fold_chunk_jit()

    def wrong(batch, dim, modulus, backend):
        part, counts = fold(batch, dim, modulus, backend)
        return part.at[dim // 2].add(1), counts

    return wrong
