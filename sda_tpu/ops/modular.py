"""Modular arithmetic with Rust signed-remainder semantics.

The reference does all group math with Rust's ``%``, which truncates toward
zero: ``-7 % 5 == -2`` (see e.g. additive share generation,
/root/reference/client/src/crypto/sharing/additive.rs:47, and the final sign
fix-up ``positive()`` at client/src/receive.rs:14-20). Python's ``%`` floors
instead, so every hot-path reduction here goes through ``rust_rem``:
``numpy.fmod`` on host, ``lax.rem`` on device — both truncate.

Values are kept in ``(-m, m)`` throughout, exactly like the reference's
in-flight share values; ``positive()`` lifts to ``[0, m)`` at the very end.

Products for moduli < 2**31 fit int64; the int64 path is the correctness
baseline on all backends. (TPUs emulate int64 with 32-bit lanes — the perf
plane replaces these with limb-decomposed int32/MXU kernels, see
``sda_tpu/parallel``.)
"""

from __future__ import annotations

import numpy as np

# Fast int64 plane: exact only for moduli below 2**31 (products < 2**62,
# sums of < 2**32 reduced terms). Larger moduli (up to WIDE_MAX_MODULUS,
# covering the 61-bit federated config) route through the wide paths:
# halving mod-sums (pair sums < 2**63 stay exact) and exact object-dtype /
# limb-space multiplication.
MAX_SAFE_MODULUS = 1 << 31
WIDE_MAX_MODULUS = 1 << 62


def rust_rem_np(x, m):
    """Truncated remainder (Rust ``%``) for numpy arrays / scalars."""
    return np.fmod(x, m)


def rust_rem_int(x: int, m: int) -> int:
    """Truncated remainder for python ints."""
    r = abs(x) % m
    return -r if x < 0 else r


def positive(x, m):
    """Lift representatives from ``(-m, m)`` to canonical ``[0, m)``.

    Mirrors ``RecipientOutput::positive`` (client/src/receive.rs:14-20).
    Works for numpy arrays and python ints.
    """
    if isinstance(x, (int, np.integer)):
        return x + m if x < 0 else x
    x = np.asarray(x)
    return np.where(x < 0, x + m, x)


def mod_add(a, b, m):
    """(a + b) with one truncated reduction; inputs in (-m, m)."""
    return rust_rem_np(np.asarray(a, dtype=np.int64) + np.asarray(b, dtype=np.int64), m)


def mod_mul(a, b, m):
    """(a * b) % m in int64; valid for m < 2**31 (products < 2**62)."""
    return rust_rem_np(np.asarray(a, dtype=np.int64) * np.asarray(b, dtype=np.int64), m)


def mod_pow(base: int, exp: int, m: int) -> int:
    """Scalar modular exponentiation (canonical representative)."""
    return pow(base % m, exp, m)


def mod_inverse(a: int, m: int) -> int:
    """Inverse of a mod prime m (canonical representative)."""
    a = a % m
    if a == 0:
        raise ZeroDivisionError("no inverse of 0")
    return pow(a, m - 2, m)


def mod_sum_wide_np(x: np.ndarray, m: int, axis: int = 0) -> np.ndarray:
    """Exact sum-mod-m along ``axis`` for any m < 2**62.

    Halving reduction: each level pairs elements (both in (-m, m), so the
    pair sum stays within int64) and reduces, log2(n) vectorized passes.
    """
    x = np.moveaxis(np.asarray(x, dtype=np.int64), axis, 0)
    while x.shape[0] > 1:
        half = x.shape[0] // 2
        paired = rust_rem_np(x[:half] + x[half : 2 * half], m)
        if x.shape[0] % 2:
            paired = np.concatenate([paired, x[-1:]], axis=0)
        x = paired
    return x[0]


def modmatmul_np(A: np.ndarray, B: np.ndarray, m: int) -> np.ndarray:
    """Exact (A @ B) mod m.

    m < 2**31: int64 path — products reduced before the K-sum so the
    accumulator cannot overflow for any K < 2**32. Larger m (to 2**62):
    exact arbitrary-precision object-dtype path (the host protocol plane is
    not the hot loop; the device hot loop uses limb kernels instead).
    Result keeps truncated-remainder representatives in (-m, m).
    """
    if m >= MAX_SAFE_MODULUS:
        A = np.asarray(A, dtype=object)
        B = np.asarray(B, dtype=object)
        out = A @ B
        return np.vectorize(lambda v: rust_rem_int(int(v), m), otypes=[np.int64])(out)
    A = np.asarray(A, dtype=np.int64)
    B = np.asarray(B, dtype=np.int64)
    # np.abs(INT64_MIN) wraps back to INT64_MIN (negative), which would
    # poison the magnitude bound below into blessing a fast path whose
    # products can't even be formed in int64 (INT64_MIN * anything wraps
    # before any reduction can run, including the per-product path's).
    # Pre-reduce such operands into (-m, m): same residues, and the
    # resulting magnitudes (< m < 2**31) make every later bound exact.
    int64_min = np.iinfo(np.int64).min
    if (A == int64_min).any():
        A = rust_rem_np(A, m)
    if (B == int64_min).any():
        B = rust_rem_np(B, m)
    # the K-sum of raw products is bounded by K*max|A|*max|B|, so when
    # that fits the arithmetic the per-product reduction (two fmod
    # passes over a (..., K, N) intermediate — the host protocol plane's
    # hottest numpy work, ~70% of participate wall at dim 10K) collapses
    # to one matmul + one rem. The bound uses the ACTUAL operand
    # magnitudes (an O(size) amax, negligible vs the matmul), so
    # unreduced inputs degrade to the robust per-product path instead of
    # silently rounding. Representatives are unchanged for the canonical
    # nonneg inputs the protocol plane feeds (raw sum and reduced-
    # product sum are both nonneg), and stay within (-m, m) either way.
    bound = (
        A.shape[-1]
        * max(1, int(np.abs(A).max(initial=0)))
        * max(1, int(np.abs(B).max(initial=0)))
    )
    if bound < (1 << 53):
        # every partial sum < 2^53: float64 is exact and the matmul runs
        # on BLAS dgemm instead of numpy's generic int64 loop
        prod = (A.astype(np.float64) @ B.astype(np.float64)).astype(np.int64)
        return rust_rem_np(prod, m)
    if bound < (1 << 63):
        return rust_rem_np(A @ B, m)
    prods = rust_rem_np(A[..., :, None] * B[None, ...], m)  # (..., K, N)
    return rust_rem_np(prods.sum(axis=-2), m)


# ---------------------------------------------------------------------------
# JAX backend (lazy import)
# ---------------------------------------------------------------------------


def rust_rem(x, m):
    """Truncated remainder (Rust ``%``) for jax arrays; jittable."""
    import jax.numpy as jnp
    from jax import lax

    from .jaxcfg import ensure_x64

    ensure_x64()
    return lax.rem(x, jnp.asarray(m, dtype=x.dtype))


def positive_jnp(x, m):
    import jax.numpy as jnp

    return jnp.where(x < 0, x + m, x)


def mod_sum_jnp(x, m, axis):
    """Sum along ``axis`` then one truncated reduction; int64 accumulate.

    The clerk-combine hot loop (reference: elementwise ``+= ; %=`` per
    participant, client/src/crypto/sharing/combiner.rs:16-30) becomes a
    single HBM-resident reduction. Safe for < 2**32 summands with |x| < m
    < 2**31.
    """
    import jax.numpy as jnp
    from jax import lax

    from .jaxcfg import ensure_x64

    ensure_x64()
    s = jnp.sum(x.astype(jnp.int64), axis=axis)
    return lax.rem(s, jnp.asarray(m, dtype=s.dtype))


def mod_sum_wide_jnp(x, m, axis: int = 0):
    """Device halving sum-mod-m along ``axis``; exact for m < 2**62.

    Static log2 unrolled pairing (jit-friendly): pads to a power of two
    with zeros, pair sums stay within int64.
    """
    import jax.numpy as jnp
    from jax import lax

    from .jaxcfg import ensure_x64

    ensure_x64()
    x = jnp.moveaxis(x.astype(jnp.int64), axis, 0)
    n = x.shape[0]
    levels = max(1, (n - 1).bit_length())
    pad = (1 << levels) - n
    if pad:
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    mm = jnp.int64(m)
    for _ in range(levels):
        half = x.shape[0] // 2
        x = lax.rem(x[:half] + x[half:], mm)
    return x[0]


def mod_sum_auto_jnp(x, m, axis: int = 0):
    """Reduced sum-mod-m along ``axis``, exact for any ``|x| < m < 2**62``.

    Single dispatch point for the narrow/wide bound: while
    ``n*(m-1) < 2**63`` a plain int64 reduction + rem is exact (and
    fastest); past it the halving mod-sum takes over. Every reduced
    modular reduction in the engine routes through here so the bound
    logic lives in exactly one place.

    Signed-representative caveat: for MIXED-SIGN input (additive closing
    shares can be negative — truncated-remainder Rust semantics) the two
    paths can return *different signed representatives of the same
    residue*: sum-then-rem carries one signed remainder of the total,
    while the pairwise-rem tree re-signs at every level. Both are the
    correct residue mod m; only canonicalization (``positive``) makes
    them bit-identical, and everything downstream does exactly that
    (pinned by tests/test_wide_modulus.py::test_mixed_sign_residue_
    equality_across_paths). For all-nonnegative input the narrow path's
    result is canonical already.
    """
    if x.shape[axis] * (m - 1) < 2**63:
        return mod_sum_jnp(x, m, axis)
    return mod_sum_wide_jnp(x, m, axis)


def modmatmul_jnp(A, B, m):
    """Exact (A @ B) mod m on device; per-product reduction then int64 sum.

    Correctness-first path (int64 emulated on TPU). The perf plane lowers
    this to int8-limb MXU matmuls.
    """
    import jax.numpy as jnp
    from jax import lax

    from .jaxcfg import ensure_x64

    ensure_x64()
    A = A.astype(jnp.int64)
    B = B.astype(jnp.int64)
    mm = jnp.asarray(m, dtype=jnp.int64)
    prods = lax.rem(A[..., :, None] * B[None, ...], mm)
    return lax.rem(jnp.sum(prods, axis=-2), mm)


# ---------------------------------------------------------------------------
# u64 mod a modulus known at trace time, division-free
# ---------------------------------------------------------------------------


def _mulhi32_const(x, c: int):
    """High uint32 word of ``x * c``: ``x`` a uint32 array, ``c`` a python
    int below 2**32. XLA has no multiply-high; schoolbook on 16-bit halves,
    every partial product and sum below 2**32."""
    import jax.numpy as jnp

    u32 = jnp.uint32
    x1, x0 = x >> u32(16), x & u32(0xFFFF)
    c1, c0 = c >> 16, c & 0xFFFF
    w = x1 * u32(c0) + ((x0 * u32(c0)) >> u32(16))
    if c1 == 0:
        return w >> u32(16)
    w2 = x0 * u32(c1) + (w & u32(0xFFFF))
    return x1 * u32(c1) + (w >> u32(16)) + (w2 >> u32(16))


def mod_u64_const(hi, lo, m: int):
    """``(hi·2³² + lo) mod m`` as uint64, exact, with no division on the
    device: ``hi``, ``lo`` uint32 arrays, ``m`` a python int, ``0 < m <=
    2**63``.

    A chip with no integer divide emulates ``u64 % m`` as a 64-step long
    division, ~1 900 lane instructions a value (PERF.md §5). For a modulus
    known when the program is traced the quotient is a multiply-high by a
    reciprocal computed here in python integers, put right by one or two
    compare-and-subtracts; all on uint32 words, as the chip's lanes are.

    - ``m`` a power of two: a mask.
    - ``m < 2**32``: ``hi mod m`` by compare-and-subtract, then ``(that·2³²
      + lo) mod m`` as a 2-by-1 word division by the reciprocal of the
      normalised divisor (Möller & Granlund 2011, algorithm 4).
    - else the quotient is below 2**32: ``q = mulhi(hi, floor(2**(64+e)/m))
      >> e`` is it or one short (two where ``e`` finds no room), and
      ``u - q·m`` is taken mod 2**64.
    """
    import jax.numpy as jnp

    u32, u64 = jnp.uint32, jnp.uint64
    if not (0 < m <= 1 << 63):
        raise ValueError(f"modulus out of range: {m}")
    bits = m.bit_length()

    def join(r_hi, r_lo):
        return (r_hi.astype(u64) << u64(32)) | r_lo.astype(u64)

    if m & (m - 1) == 0:
        mask = m - 1
        return join(hi & u32(mask >> 32), lo & u32(mask & 0xFFFFFFFF))

    if bits <= 32:
        # hi mod m: hi < 2**(s+1)·m, a compare-and-subtract for each bit
        s = 32 - bits
        r = hi
        for j in reversed(range(s + 1)):
            r = jnp.where(r >= u32(m << j), r - u32(m << j), r)
        # (r·2³² + lo) mod m, dividend and divisor shifted until the
        # divisor's top bit is set: (u1·2³² + u0) mod d, u1 < d
        d = m << s
        v = ((1 << 64) - 1) // d - (1 << 32)
        u1, u0 = ((r << u32(s)) | (lo >> u32(bits)), lo << u32(s)) if s else (r, lo)
        q0 = u1 * u32(v) + u0
        q1 = _mulhi32_const(u1, v) + u1 + (q0 < u0).astype(u32) + u32(1)
        r = u0 - q1 * u32(d)
        r = jnp.where(r > q0, r + u32(d), r)
        r = jnp.where(r >= u32(d), r - u32(d), r)
        return (r >> u32(s)).astype(u64)

    # 2**32 < m: the quotient has 65 - bits bits. A reciprocal of 16 bits
    # halves the multiply-high where that still leaves e >= 1.
    e = bits - 49 if bits >= 50 else bits - 33
    k = (1 << (64 + e)) // m  # < 2**16, or < 2**32
    q = _mulhi32_const(hi, k) >> u32(e)
    # u/m - q < 1 + 2**32/m + 2**-e: one correction where that is <= 2
    corrections = 1 if (1 << (32 + e)) + m <= (m << e) else 2
    m_hi, m_lo = m >> 32, m & 0xFFFFFFFF
    p_lo = q * u32(m_lo)
    p_hi = q * u32(m_hi)
    if (65 - bits) + m_lo.bit_length() > 32:
        p_hi = p_hi + _mulhi32_const(q, m_lo)

    def sub64(a_hi, a_lo, b_hi, b_lo):
        return a_hi - b_hi - (a_lo < b_lo).astype(u32), a_lo - b_lo

    r_hi, r_lo = sub64(hi, lo, p_hi, p_lo)
    for _ in range(corrections):
        ge = (r_hi > u32(m_hi)) | ((r_hi == u32(m_hi)) & (r_lo >= u32(m_lo)))
        s_hi, s_lo = sub64(r_hi, r_lo, u32(m_hi), u32(m_lo))
        r_hi, r_lo = jnp.where(ge, s_hi, r_hi), jnp.where(ge, s_lo, r_lo)
    return join(r_hi, r_lo)
