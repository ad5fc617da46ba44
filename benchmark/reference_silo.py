"""The plain reference of the few-fat-rows round: what the aggregate of the
resident rows is, at a dim of 10⁸.

Independent of the code under test: imports nothing from ``sda_tpu``. The
aggregate is the column sum of every row mod p; it does not depend on the
share keys, so it is computed once in set-up. :mod:`benchmark.reference`
computes the same thing for the narrow cells, but widens the whole ``(2,
dim)`` of half sums to python integers: at 1.1e8 columns that is 2.2e8 python
objects and about 9 GB of host memory.

Two computations, which must agree where they overlap:

* on the device, in plain ``jax.numpy``: the rows' column sums, kept as their
  high and low uint32 words and added one row at a time with the carry
  (:func:`add_rows`, traced inside the jitted call that makes each chunk).
  They cross to the host as 32-bit words (a 32-bit array crosses at 3.3 GB/s,
  a 64-bit one at 0.21), and the host joins them and reduces mod p in
  machine integers, one block of columns at a time into buffers it keeps
  (:func:`aggregate`): a column sum of ``rows`` values below ``2^(bits(p) −
  1)`` stays below 2^64 while ``rows`` is at most 16;
* on the host: about 1 024 strided columns are fetched whole and summed in
  python integers, with no words at all.
"""

from __future__ import annotations

import numpy as np

#: about this many columns are fetched and summed on the host
HOST_CHECK_COLUMNS = 1024

#: columns the host joins and reduces at once
BLOCK_COLUMNS = 1 << 22


def add_rows(words, block):
    """``(hi, lo)`` uint32 running column sums with every row of ``block``
    (``(rows, dim)`` canonical integers) added, each word with its carry.
    Traced inside the jitted call that makes the input."""
    import jax.numpy as jnp

    hi, lo = words
    wide = block.astype(jnp.uint64)
    for row in range(block.shape[0]):
        value = wide[row]
        low = value.astype(jnp.uint32)
        total = lo + low
        hi = hi + (value >> jnp.uint64(32)).astype(jnp.uint32) + (total < lo).astype(jnp.uint32)
        lo = total
    return hi, lo


def host_check_stride(dim: int) -> int:
    return max(1, dim // HOST_CHECK_COLUMNS)


def strided_columns(block):
    """The columns the host sums itself, ``(rows, ~1024)``."""
    return block[:, :: host_check_stride(block.shape[-1])]


def aggregate(hi, lo, columns_host, modulus: int, rows: int) -> np.ndarray:
    """``(dim,)`` int64 canonical aggregate of ``rows`` rows from the
    device's column sums as their uint32 words ``hi``, ``lo``; raises if the
    host's own sums of the strided columns disagree."""
    if rows << (int(modulus).bit_length() - 1) > 1 << 64:
        raise ValueError("the column sums leave 64 bits: at most 16 rows of the field's values")
    hi, lo = np.asarray(hi), np.asarray(lo)
    dim = hi.shape[0]
    want = np.empty(dim, dtype=np.int64)
    block = min(dim, BLOCK_COLUMNS)
    wide = np.empty(block, dtype=np.uint64)
    low = np.empty(block, dtype=np.uint64)
    for start in range(0, dim, block):
        end = min(dim, start + block)
        n = end - start
        np.copyto(wide[:n], hi[start:end], casting="unsafe")
        np.left_shift(wide[:n], np.uint64(32), out=wide[:n])
        np.copyto(low[:n], lo[start:end], casting="unsafe")
        np.bitwise_or(wide[:n], low[:n], out=wide[:n])
        np.remainder(wide[:n], np.uint64(modulus), out=wide[:n])
        np.copyto(want[start:end], wide[:n], casting="unsafe")
    cols = np.asarray(columns_host).astype(object)
    host = cols.sum(axis=0) % modulus
    if not np.array_equal(want[:: host_check_stride(dim)], host.astype(np.int64)):
        raise AssertionError(
            "reference disagrees with itself: device column sums != host "
            "python-integer sums on the strided columns"
        )
    return want
