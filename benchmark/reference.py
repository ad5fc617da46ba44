"""The plain reference: what the aggregate of the resident input is.

Independent of the code under test: imports nothing from ``sda_tpu``. The
aggregate of a round is the column sum of every participant's vector mod p;
it does not depend on the share keys, so it is computed once in set-up.

Two computations, which must agree where they overlap:

* on the device, in plain ``jax.numpy``: every value (canonical, below p <
  2^62) is split into halves below 2^32, each half is summed down the rows
  in int64 (exact below 2^31 rows), and the two half sums are widened once,
  on the host, in python integers;
* on the host: about 1 024 strided columns are fetched whole and summed in
  python integers, with no splitting at all (rows are first added in plain
  int64 in groups too small to wrap, which is exact).
"""

from __future__ import annotations

import numpy as np

#: about this many columns are fetched and summed on the host
HOST_CHECK_COLUMNS = 1024


def half_sums(block):
    """``(rows, dim)`` canonical integers -> ``(2, dim)`` int64: the sums down
    the rows of the values' low and high 32-bit halves. Traced inside the
    jitted call that makes the input, chunk by chunk."""
    import jax.numpy as jnp

    if jnp.iinfo(block.dtype).bits <= 32:
        # values below 2^31: the low half is the value, the high half zero
        lo = block.astype(jnp.uint32).astype(jnp.int64)
        hi = None
    else:
        wide = block.astype(jnp.int64)
        lo = wide & jnp.int64(0xFFFFFFFF)
        hi = wide >> jnp.int64(32)
    lo_sum = jnp.sum(lo, axis=0)
    hi_sum = jnp.zeros_like(lo_sum) if hi is None else jnp.sum(hi, axis=0)
    return jnp.stack([lo_sum, hi_sum])


def host_check_stride(dim: int) -> int:
    return max(1, dim // HOST_CHECK_COLUMNS)


def strided_columns(block):
    """The columns the host sums itself, ``(rows, ~1024)``."""
    return block[:, :: host_check_stride(block.shape[-1])]


def aggregate(half_sums_host, columns_host, modulus: int, passes: int, rows: int):
    """``(dim,)`` int64 canonical aggregate of ``passes`` folds of the
    block, from the device's half sums; raises if the host's own sums of
    the strided columns disagree."""
    if rows >= 1 << 31:
        raise ValueError("half sums are exact in int64 below 2^31 rows")
    halves = np.asarray(half_sums_host).astype(object)
    exact = halves[0] + halves[1] * (1 << 32)  # widened once, python ints
    want = (exact * passes) % modulus
    cols = np.asarray(columns_host).astype(np.int64)
    # rows whose plain int64 sum cannot wrap; between groups, python integers
    group = max(1, ((1 << 63) - 1) // (int(modulus) - 1))
    host = np.zeros(cols.shape[1], dtype=object)
    for start in range(0, cols.shape[0], group):
        host = host + cols[start : start + group].sum(axis=0).astype(object)
    host = (host * passes) % modulus
    stride = host_check_stride(want.shape[0])
    if not np.array_equal(want[::stride], host):
        raise AssertionError(
            "reference disagrees with itself: device half sums != host "
            "python-integer sums on the strided columns"
        )
    return want.astype(np.int64)
