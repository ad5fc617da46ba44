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

from .. import telemetry

# Fast int64 plane: exact only for moduli below 2**31 (products < 2**62,
# sums of < 2**32 reduced terms). Larger moduli (up to WIDE_MAX_MODULUS,
# covering the 61-bit federated config) route through the wide paths:
# halving mod-sums (pair sums < 2**63 stay exact) and limb-space
# multiplication reduced by ``mod_limbs_np``, all in machine integers.
MAX_SAFE_MODULUS = 1 << 31
WIDE_MAX_MODULUS = 1 << 62

#: quotient bound of ``mod_limbs_np``: its float64 quotient estimate is
#: within 1 of the true one while the true one stays below this
_MAX_LIMB_QUOTIENT = 1 << 50
#: most limbs ``mod_limbs_np`` takes (each adds a rounding to the estimate)
_MAX_LIMBS = 4


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


def mod_limbs_np(limbs, shift: int, p: int) -> np.ndarray:
    """``(Σ_j limbs[j] · 2**(shift·j)) mod p``, canonical in ``[0, p)``,
    exact, in machine integers: the one reduction under the wide host paths
    (recombine, ``modmatmul_np``'s wide branch).

    ``limbs``: at most 4 int64 arrays of one shape, every entry
    non-negative; ``1 < p < 2**62``; the value ``V`` they spell must stay
    below ``2**50 · p``. All three are checked (the last from each limb's
    maximum) and a breach raises ``ValueError``: nothing is trusted.

    Why it is exact. ``V`` itself fits no machine integer, but two images
    of it do. In float64, ``x = V_float / p`` carries a relative error of
    at most ``(len(limbs) + 2) · 2**-53`` (one rounding a limb's
    conversion and addition, one for ``float(p)``, one for the division;
    the weights are powers of two), so with ``V / p < 2**50`` and four
    limbs ``|x − V/p| < 6/8`` and ``q̂ = floor(x)`` is the true quotient or
    one off either way. Mod 2**64, in uint64 wrap-around arithmetic,
    ``V − q̂·p`` is formed exactly; the true difference lies in
    ``[−p, 2p)``, inside int64 since ``2p < 2**63``, so reading the
    wrapped bits as int64 gives it, and one ``±p`` makes it canonical.
    """
    if not 1 < p < WIDE_MAX_MODULUS:
        raise ValueError(f"modulus out of range: {p}")
    limbs = [np.asarray(t, dtype=np.int64) for t in limbs]
    if not 1 <= len(limbs) <= _MAX_LIMBS:
        raise ValueError(f"1 to {_MAX_LIMBS} limbs, got {len(limbs)}")
    shape = limbs[0].shape
    tops = []
    for j, t in enumerate(limbs):
        if t.shape != shape:
            raise ValueError(f"limb {j} has shape {t.shape}, limb 0 {shape}")
        if t.size and int(t.min()) < 0:
            raise ValueError(f"limb {j} has a negative entry")
        tops.append(int(t.max(initial=0)))
    if sum(top << (shift * j) for j, top in enumerate(tops)) >= _MAX_LIMB_QUOTIENT * p:
        raise ValueError("limbs spell a value of 2**50 · p or more; reduce them first")
    v_float = np.zeros(limbs[0].size, dtype=np.float64)
    v_wrap = np.zeros(limbs[0].size, dtype=np.uint64)
    for j, t in enumerate(limbs):
        if not tops[j]:
            continue  # adds nothing (and its weight may be beyond a float)
        t = t.reshape(-1)  # 1-d: numpy's scalars warn where its arrays wrap
        weight = 1 << (shift * j)
        v_float += t.astype(np.float64) * float(weight)
        v_wrap += t.astype(np.uint64) * np.uint64(weight & 0xFFFFFFFFFFFFFFFF)
    quotient = np.floor(v_float / p).astype(np.uint64)
    r = (v_wrap - quotient * np.uint64(p)).view(np.int64)
    r += np.where(r < 0, np.int64(p), np.int64(0))
    r -= np.where(r >= p, np.int64(p), np.int64(0))
    return r.reshape(shape)


def count_wide_product(path: str) -> None:
    """One host mod-p product at a modulus of 2**31 or more, by the road it
    took: ``limb`` (``mod_limbs_np``, machine integers) or ``object``
    (python integers). A run reads from it how often the vectorised branch
    engaged and whether anything still fell back."""
    telemetry.counter(
        "sda_wide_mod_products_total",
        "host mod-p products at moduli >= 2**31, by path (limb | object)",
        path=path,
    ).inc()


def _wide_operands(A, B, m: int):
    """``(A, B)`` as canonical int64 arrays in ``[0, m)`` where the limb
    road can take them, else ``None`` and the object road does: a modulus
    of 2**62 or more, an entry no int64 holds, a negative entry (the object
    road's result carries the exact product's sign, which machine integers
    cannot tell), an empty operand, or shapes other than ``(..., K) @ (K,
    N)``. Non-negative entries of ``m`` or more are reduced here, exactly."""
    if m >= WIDE_MAX_MODULUS:
        return None
    try:
        A = np.asarray(A).astype(np.int64, casting="unsafe", copy=False)
        B = np.asarray(B).astype(np.int64, casting="unsafe", copy=False)
    except (OverflowError, TypeError, ValueError):
        return None
    if not A.size or not B.size or A.ndim < 1 or B.ndim != 2 or A.shape[-1] != B.shape[0]:
        return None
    if A.min() < 0 or B.min() < 0:
        return None
    if A.max() >= m:
        A = A % m
    if B.max() >= m:
        B = B % m
    return A, B


def modmatmul_path(A, B, m: int) -> str:
    """The road ``modmatmul_np(A, B, m)`` takes, for a span's ``path``
    attribute: ``int64`` below 2**31 (one machine-integer matmul and rem),
    else ``limb`` or ``object`` as ``_wide_operands`` decides from the
    operands."""
    if m < MAX_SAFE_MODULUS:
        return "int64"
    return "object" if _wide_operands(A, B, m) is None else "limb"


def _modmatmul_limbs(A: np.ndarray, B: np.ndarray, m: int) -> np.ndarray:
    """``(A @ B) mod m``, canonical, for canonical int64 ``A`` ``(..., K)``
    and ``B`` ``(K, N)``, ``2**31 <= m < 2**62``, without a python integer.

    With ``w = ceil(bits(m) / 3)``, ``A = Σ_i a_i·2**(w·i)`` in three limbs
    below ``2**w``, and ``A @ B ≡ Σ_i a_i @ B_i`` for ``B_i = 2**(w·i)·B mod
    m`` (each made from the one before by ``mod_limbs_np``: a quotient below
    ``2**w``). Split every ``B_i`` the same way, ``B_i = Σ_j b_ij·2**(w·j)``,
    and the product is ``Σ_j T_j·2**(w·j)`` with ``T_j = [a_0|a_1|a_2] @
    [b_0j; b_1j; b_2j]``: one matmul of ``(M, 3K) @ (3K, 3N)`` whose
    entries stay below ``3K·2**(2w)``, exact in float64 (so on BLAS) while
    that is under 2**53; a longer ``K`` is cut into chunks that keep it so,
    their residues added mod m. The three ``T_j`` go through
    ``mod_limbs_np`` (``V < 3K·2**(4w+1)`` is far below ``2**50·m`` for
    every ``w >= 11``, and checked there).
    """
    if A.ndim == 2 and B.size > A.size:
        # the weights are folded into the smaller operand
        return _modmatmul_limbs(B.T, A.T, m).T
    w = -(-m.bit_length() // 3)
    mask = np.int64((1 << w) - 1)
    K, N = B.shape
    lead, A = A.shape[:-1], A.reshape(-1, K)

    def split(x):
        return np.concatenate([(x >> np.int64(w * i)) & mask for i in range(3)], axis=1)

    scaled, folded = B, []
    for i in range(3):
        if i:
            scaled = mod_limbs_np([np.zeros_like(scaled), scaled], w, m)
        folded.append(split(scaled))  # (K, 3N): b_i0 | b_i1 | b_i2
    chunk = ((1 << 53) - 1) // (3 * int(mask) ** 2)
    out = None
    for k0 in range(0, K, chunk):
        k1 = min(K, k0 + chunk)
        a_limbs = split(A[:, k0:k1]).astype(np.float64)  # (M, 3·(k1-k0))
        b_limbs = np.concatenate([f[k0:k1] for f in folded], axis=0).astype(np.float64)
        # transposed, (3N, 3K) @ (3K, M): every T_j comes out contiguous, and
        # with the long axis last the skinny dgemm was the quicker and the
        # steadier on the hosts it was timed on (PERF.md §6, PR 29)
        T = (b_limbs.T @ a_limbs.T).astype(np.int64)  # T_0; T_1; T_2, each (N, M)
        part = mod_limbs_np([T[j * N : (j + 1) * N] for j in range(3)], w, m)
        if out is None:
            out = part
        else:
            out += part
            out -= np.where(out >= m, np.int64(m), np.int64(0))
    return out.T.reshape(lead + (N,))


def modmatmul_np(A: np.ndarray, B: np.ndarray, m: int) -> np.ndarray:
    """Exact (A @ B) mod m.

    m < 2**31: int64 path — products reduced before the K-sum so the
    accumulator cannot overflow for any K < 2**32. Larger m (to 2**62),
    non-negative operands (all the fabric and the share build feed): three
    limbs of a third of m's width, one float64 matmul and ``mod_limbs_np``
    (``_modmatmul_limbs``), canonical result, no python integer. What that
    road cannot take (``_wide_operands``: a negative entry, m >= 2**62,
    values beyond int64) takes the arbitrary-precision object-dtype road
    it took before; the choice is made from the operands, and both roads
    return the same bits wherever both apply. ``sda_wide_mod_products_total``
    counts the wide calls by road.
    Result keeps truncated-remainder representatives in (-m, m).
    """
    if m >= MAX_SAFE_MODULUS:
        operands = _wide_operands(A, B, m)
        if operands is not None:
            count_wide_product("limb")
            return _modmatmul_limbs(*operands, m)
        count_wide_product("object")
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
    """Device halving sum-mod-m along ``axis``; exact for ``|x| < m < 2**62``.

    Static log2 unrolled pairing (jit-friendly): pads to a power of two
    with zeros, pair sums stay within int64. A pair sum lies inside
    ``(-2m, 2m)``, so its truncated remainder is the sum less m where it
    reaches m and plus m where it reaches -m: what ``lax.rem`` gives, sign
    for sign, without the 64-step division a chip with no integer divide
    makes of it.
    """
    import jax.numpy as jnp

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
        s = x[:half] + x[half:]
        x = jnp.where(s >= mm, s - mm, jnp.where(s <= -mm, s + mm, s))
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


# ---------------------------------------------------------------------------
# a·c mod p at up to 62 bits on uint32 words: Shoup's precomputed quotient
# ---------------------------------------------------------------------------


def _mul32(x, y):
    """``(hi, lo)`` uint32 words of the 64-bit product of uint32 ``x`` and
    ``y``. The chip has no multiply-high: schoolbook on 16-bit halves, every
    partial product and sum below 2**32."""
    import jax.numpy as jnp

    u32 = jnp.uint32
    x1, x0 = x >> u32(16), x & u32(0xFFFF)
    y1, y0 = y >> u32(16), y & u32(0xFFFF)
    p00, p01, p10 = x0 * y0, x0 * y1, x1 * y0
    mid = (p00 >> u32(16)) + (p01 & u32(0xFFFF)) + (p10 & u32(0xFFFF))
    hi = x1 * y1 + (p01 >> u32(16)) + (p10 >> u32(16)) + (mid >> u32(16))
    return hi, x * y


def _add32(a, b):
    """``(carry, a + b mod 2**32)`` of uint32 words, the carry 0 or 1."""
    import jax.numpy as jnp

    s = a + b
    return (s < a).astype(jnp.uint32), s


def add64(a_hi, a_lo, b_hi, b_lo):
    """``a + b mod 2**64`` on uint32 word pairs."""
    carry, lo = _add32(a_lo, b_lo)
    return a_hi + b_hi + carry, lo


def shoup_words(c: int, p: int) -> tuple:
    """The four uint32 words that :func:`mulmod_shoup` takes for the
    multiplier ``c`` (``0 <= c < p``): ``(c_hi, c_lo, w_hi, w_lo)``, ``w =
    floor(c·2**64 / p)``, the quotient worked out here in python integers."""
    if not 1 < p < 1 << 62:
        raise ValueError(f"modulus out of range: {p}")
    if not 0 <= c < p:
        raise ValueError(f"the multiplier is canonical, in [0, {p}): not {c}")
    w = (c << 64) // p
    return c >> 32, c & 0xFFFFFFFF, w >> 32, w & 0xFFFFFFFF


def mulmod_shoup(a_hi, a_lo, c_words, p: int):
    """``a·c mod p`` as its uint32 words ``(hi, lo)``, exact, with no
    division and no 64-bit type: ``a`` any value below 2**64 as uint32 word
    arrays, ``c`` canonical and fixed for the call, given as
    :func:`shoup_words` makes them (python ints, folded into the program, or
    uint32 arrays that broadcast against ``a``: a matrix handed in as an
    operand), ``1 < p < 2**62`` a python int.

    Shoup's product (Shoup, NTL; Harvey 2014, "Faster arithmetic for
    number-theoretic transforms", algorithm 2): with ``w = floor(c·2**64/p)``
    the quotient ``q = floor(a·w / 2**64)`` is the true ``floor(a·c/p)`` or
    one short, for every ``a < 2**64``, so ``r = a·c − q·p`` lies in ``[0,
    2p)`` and is taken mod 2**64 from the low words alone; one compare and
    subtract makes it canonical. ``q`` is the exact high half of the 128-bit
    ``a·w``: four 32-bit products and their carries."""
    import jax.numpy as jnp

    u32 = jnp.uint32
    if not 1 < p < 1 << 62:
        raise ValueError(f"modulus out of range: {p}")
    c_hi, c_lo, w_hi, w_lo = (jnp.asarray(x, dtype=u32) for x in c_words)
    # q = high 64 bits of a·w
    h00, l00 = _mul32(a_lo, w_lo)
    h01, l01 = _mul32(a_lo, w_hi)
    h10, l10 = _mul32(a_hi, w_lo)
    h11, l11 = _mul32(a_hi, w_hi)
    c1, mid = _add32(h00, l01)
    c2, _ = _add32(mid, l10)
    c3, q_lo = _add32(l11, h01)
    c4, q_lo = _add32(q_lo, h10)
    c5, q_lo = _add32(q_lo, c1 + c2)
    q_hi = h11 + c3 + c4 + c5
    # a·c and q·p mod 2**64
    ac_hi, ac_lo = _mul32(a_lo, c_lo)
    ac_hi = ac_hi + a_lo * c_hi + a_hi * c_lo
    p_hi, p_lo = p >> 32, p & 0xFFFFFFFF
    qp_hi, qp_lo = _mul32(q_lo, u32(p_lo))
    qp_hi = qp_hi + q_lo * u32(p_hi) + q_hi * u32(p_lo)
    r_hi = ac_hi - qp_hi - (ac_lo < qp_lo).astype(u32)
    r_lo = ac_lo - qp_lo
    ge = (r_hi > u32(p_hi)) | ((r_hi == u32(p_hi)) & (r_lo >= u32(p_lo)))
    s_hi = r_hi - u32(p_hi) - (r_lo < u32(p_lo)).astype(u32)
    s_lo = r_lo - u32(p_lo)
    return jnp.where(ge, s_hi, r_hi), jnp.where(ge, s_lo, r_lo)
