"""Exact mod-p matmul on the MXU via base-128 limb decomposition.

TPUs have no native 64-bit integer multiply; XLA emulates int64 products in
many 32-bit VPU ops. But the MXU multiplies int8 x int8 -> int32 natively
and fast. So: decompose canonical residues (0 <= x < p < 2^31) into
base-128 limbs (values 0..127, stored int8), matmul every limb pair on the
MXU, and recombine partials with ``128^(i+j) mod p`` weights in int64.

Exactness bounds: each partial product <= 127*127; an int32 accumulator
holds K <= 2^31 / 127^2 = ~133k contraction elements. The share matmul
contracts over k+t (tiny); bigger contractions would chunk K. The limb
count is ceil(bits(p)/7), so a 31-bit modulus costs 25 int8 matmuls —
still far cheaper on the MXU than one emulated int64 matmul on the VPU.
"""

from __future__ import annotations


from ..ops.jaxcfg import ensure_x64

def _max_contraction(L: int) -> int:
    """int32 bound for one weight group: up to L partial matmuls summed,
    each elementwise <= K * 127^2."""
    return (1 << 31) // (127 * 127 * L)


def limb_count(p: int) -> int:
    return -(-p.bit_length() // 7)


def limb_partials(A, B, p: int):
    """Weight-grouped limb partial products of (M, K) @ (K, N) mod p.

    Returns int32 ``(W, M, N)`` with ``W = 2*L-1`` such that the true
    product is ``sum_w partials[w] * 128^w (mod p)``. This is the MXU-only
    piece: recombination (the int64 multiply/rem work) can be deferred —
    crucially, *summed over batch axes first* (linearity), which is how the
    clerk-combine keeps all mod-p arithmetic out of the participant loop.
    """
    ensure_x64()
    import jax.numpy as jnp
    from jax import lax

    K = A.shape[-1]
    L = limb_count(p)
    if K > _max_contraction(L):
        raise ValueError(f"contraction {K} overflows int32 accumulator; chunk first")

    def limbs(x, count):
        # canonical values < p < 2^31 fit int32: extract limbs in 32-bit
        # lanes (native on TPU) instead of emulated 64-bit shifts
        x = x.astype(jnp.int32) if p <= (1 << 31) else x.astype(jnp.int64)
        seven = x.dtype.type(0x7F)
        return [
            ((x >> x.dtype.type(7 * i)) & seven).astype(jnp.int8) for i in range(count)
        ]

    a_limbs = limbs(A, L)
    b_limbs = limbs(B, L)
    partials = [None] * (2 * L - 1)
    for i in range(L):
        for j in range(L):
            prod = lax.dot_general(
                a_limbs[i],
                b_limbs[j],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )
            w = i + j
            partials[w] = prod if partials[w] is None else partials[w] + prod
    return jnp.stack(partials)  # (W, M, N) int32


def limb_recombine(partials, p: int):
    """(W, ...) partials (each < 2^31) -> canonical mod-p values.

    int64 multiply + rem on whatever shape you pass — call this on the
    *reduced* accumulator, never inside the hot loop.
    """
    ensure_x64()
    import jax.numpy as jnp
    from jax import lax

    if p >= (1 << 31):
        raise ValueError(
            "device recombine needs p < 2^31 (weight products would overflow "
            "int64); reduce the accumulator and use limb_recombine_host"
        )
    W = partials.shape[0]
    weights = jnp.asarray(
        [pow(128, w, p) for w in range(W)], dtype=jnp.int64
    ).reshape((W,) + (1,) * (partials.ndim - 1))
    acc = jnp.sum(
        lax.rem(partials.astype(jnp.int64) * weights, jnp.int64(p)), axis=0
    )
    return lax.rem(acc, jnp.int64(p))


def limb_modmatmul(A, B, p: int):
    """(M, K) @ (K, N) mod p, inputs canonical [0, p), output canonical.

    Jittable; int8 MXU matmuls inside, int64 only in the recombine. When
    the product feeds a sum over a batch axis, prefer ``limb_partials`` +
    reduce + ``limb_recombine`` to keep the int64 work off the big tensor.
    """
    return limb_recombine(limb_partials(A, B, p), p)


def fold_const_limbs(B_host, p: int):
    """Weight-folded limb decomposition of a *constant* matrix B (K, N).

    For a host-known B (the share matrix: ops/shamir.py precomputes it once
    per scheme), the cross-limb weight structure can be folded into B ahead
    of time:  ``A @ B = Σ_i a_i·128^i @ B = Σ_i a_i @ (128^i·B mod p)``.
    Decomposing each ``D_i = 128^i·B mod p`` back into base-128 limbs
    ``d_{i,m}`` and stacking the ``i`` axis onto the contraction gives

        ``A @ B ≡ Σ_m 128^m · (A_limbs @ stacks[m])  (mod p)``

    with ``A_limbs = [a_0 | … | a_{L-1}]`` of shape (M, L·K). Compared to
    the generic ``limb_partials`` this is L matmuls instead of L² and L
    weight groups instead of 2L−1 — and each partial is bounded by
    ``L·K·127²``, small enough that the whole recombine needs ONE int64
    ``rem`` at the very end (no per-weight division on the big tensor).

    Returns int8 ``(L, L·K, N)`` stacks. Exact for any p (host python-int
    arithmetic); device recombine still requires p < 2^31.
    """
    import numpy as np

    L = limb_count(p)
    B_obj = np.asarray(B_host, dtype=object)
    K, N = B_obj.shape
    stacks = np.empty((L, L * K, N), dtype=np.int8)
    for i in range(L):
        D_i = (pow(128, i, p) * B_obj) % p
        for m in range(L):
            stacks[m, i * K : (i + 1) * K] = ((D_i >> (7 * m)) & 0x7F).astype(
                np.int8
            )
    return stacks


def limb_partials_const(A, stacks, p: int):
    """Weight-grouped partials of ``A @ B mod p`` from ``fold_const_limbs(B)``.

    ``A`` (M, K) canonical; returns int32 ``(L, M, N)`` such that the true
    product is ``Σ_m partials[m]·128^m (mod p)`` — drop-in for
    ``limb_partials`` (just a shorter weight axis) wherever B is constant,
    e.g. the fused share+combine hot loop. Each partial ≤ L·K·127².
    """
    ensure_x64()
    import jax.numpy as jnp
    from jax import lax

    L, LK, N = stacks.shape
    K = LK // L
    if A.shape[-1] != K:
        raise ValueError(f"A contraction {A.shape[-1]} != stacks K {K}")
    if LK * 127 * 127 >= (1 << 31):
        raise ValueError(f"contraction {LK} overflows int32 accumulator")

    import jax

    with jax.named_scope("fabric.share_matmul/limbs"):
        x = A.astype(jnp.int32) if p <= (1 << 31) else A.astype(jnp.int64)
        seven = x.dtype.type(0x7F)
        a_limbs = jnp.concatenate(
            [((x >> x.dtype.type(7 * i)) & seven).astype(jnp.int8) for i in range(L)],
            axis=-1,
        )  # (M, L*K)
        if jax.default_backend() == "cpu":
            # XLA's CPU emitter mis-fuses the int64->int8 limb extraction into
            # the int8 dot for some degenerate shapes (k=1 wide), producing
            # invalid IR ("add i32, i8"). A barrier cuts that fusion; the TPU
            # path (where a_limbs materializes for the L dots anyway) is left
            # untouched.
            a_limbs = lax.optimization_barrier(a_limbs)
    with jax.named_scope("fabric.share_matmul/dot"):
        partials = [
            lax.dot_general(
                a_limbs,
                jnp.asarray(stacks[m]),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )
            for m in range(L)
        ]
        return jnp.stack(partials)  # (L, M, N) int32


def limb_modmatmul_const(A, B_host, p: int):
    """(M, K) @ const (K, N) mod p with one final division.

    The single-rem recombine is exact because every partial is bounded by
    ``L·K·127²`` (not 2^31): the weighted int64 accumulator stays below
    ``L · L·K·127² · (p−1)``, checked against 2^63 at trace time.
    """
    ensure_x64()
    import jax.numpy as jnp
    from jax import lax

    if p >= (1 << 31):
        raise ValueError(
            "device recombine needs p < 2^31; use limb_partials_const + "
            "reduce + limb_recombine_host"
        )
    stacks = fold_const_limbs(B_host, p)
    L, LK, _ = stacks.shape
    if L * (LK * 127 * 127) * (p - 1) >= (1 << 63):
        # fall back to per-weight reduction (never hit at SDA shapes)
        return limb_recombine(limb_partials_const(A, stacks, p), p)
    partials = limb_partials_const(A, stacks, p)
    weights = jnp.asarray([pow(128, m, p) for m in range(L)], dtype=jnp.int64)
    acc = jnp.sum(
        partials.astype(jnp.int64) * weights.reshape((L,) + (1,) * (partials.ndim - 1)),
        axis=0,
    )
    return lax.rem(acc, jnp.int64(p))


def limb_recombine_host(partials, p: int):
    """Exact host recombine ``sum_w partials[w] * 128^w mod p`` of the tiny
    ``(W, batches, clerks)`` accumulator, for any modulus width (the device
    recombine overflows int64 from p = 2^31 up). Machine integers: below
    2^31 every reduced term times its weight is under 2^62, an int64
    multiply-add and ``%``; from 2^31 up it is ``modmatmul_np`` by the
    column of weights (its limb road; the span's ``path`` says which was
    taken). Returns canonical int64 values."""
    import numpy as np

    from .. import telemetry
    from ..ops.modular import MAX_SAFE_MODULUS, modmatmul_np, modmatmul_path, positive

    with telemetry.span(
        "fabric.epilogue.recombine", modulus_bits=int(p).bit_length(), shape=np.shape(partials)
    ) as record:
        arr = np.asarray(partials)
        weights = [pow(128, w, p) for w in range(arr.shape[0])]
        if p < MAX_SAFE_MODULUS and arr.dtype.kind == "i":
            path = "int64"
            out = np.zeros(arr.shape[1:], dtype=np.int64)
            for w, weight in enumerate(weights):
                out = (out + (arr[w].astype(np.int64, copy=False) % p) * weight) % p
        else:
            rows = np.moveaxis(arr, 0, -1)  # (..., W)
            column = np.array(weights, dtype=np.int64).reshape(-1, 1)
            path = modmatmul_path(rows, column, p)
            out = positive(modmatmul_np(rows, column, p), p)[..., 0]
        if record is not None:
            record["attrs"]["path"] = path
        return out
