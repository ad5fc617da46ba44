"""Randomness for masks and shares.

Host path: OS entropy (``os.urandom``) with vectorized rejection sampling —
unbiased uniform draws in ``[0, m)``, the numpy equivalent of the reference's
``OsRng.gen_range(0, m)`` (client/src/crypto/sharing/additive.rs:42-44).

Device path: counter-based draws from JAX's threefry PRNG for
simulation/benchmark workloads (1M synthetic participants on a TPU mesh);
uses a 64-bit draw reduced mod m, whose bias is < 2**-33 for m < 2**31 —
fine for load simulation, NOT a substitute for the host CSPRNG in real
deployments (documented trade-off). The reduction is by a reciprocal of the
modulus computed at trace time (``modular.mod_u64_const``), not the ``%``
a chip without an integer divide would emulate as a long division.
"""

from __future__ import annotations

import os

import numpy as np


def uniform_mod_host(shape, m: int, entropy=os.urandom) -> np.ndarray:
    """Unbiased uniform int64 draws in [0, m) from OS entropy.

    Large default-entropy draws route through the C ChaCha20 plane
    keyed with a fresh FULL 256-bit OS-entropy key per call (8 seed
    words — the protocol's wire-format masking seeds are 128-bit for
    interop, but this seed is ephemeral and never serialized, so there
    is no reason to cap the key) — the same primitive and (unbiased)
    rand-0.3 rejection zone the protocol's own ChaCha masking uses
    (crypto.rs:53-62; native/_sdanative.c), ~2.7x the direct-urandom
    rate at share-vector sizes. Small draws, missing native extension,
    or a custom ``entropy`` source (tests pass deterministic ones) take
    the direct OS-entropy rejection path. Both paths produce unbiased
    uniforms over [0, m).
    """
    if not (0 < m <= 1 << 63):
        raise ValueError(f"modulus out of range: {m}")
    n = int(np.prod(shape)) if shape else 1
    if entropy is os.urandom and n >= 512:
        from .. import native

        if native.available():
            seed = np.frombuffer(os.urandom(32), dtype=np.uint32)
            return native.chacha_expand(seed, n, m).reshape(shape)
    out = np.empty(n, dtype=np.int64)
    rejection = (1 << 64) % m != 0
    zone = (1 << 64) - ((1 << 64) % m)  # accept draws < zone
    filled = 0
    while filled < n:
        need = n - filled
        draw = np.frombuffer(entropy(8 * need), dtype=np.uint64)
        if rejection:
            draw = draw[draw < np.uint64(zone)]
        vals = (draw % np.uint64(m)).astype(np.int64)
        k = min(len(vals), need)
        out[filled : filled + k] = vals[:k]
        filled += k
    return out.reshape(shape)


def uniform_mod_device(key, shape, m: int):
    """Device-side uniform draws in [0, m); simulation-grade (see module doc)."""
    import jax.numpy as jnp
    from jax import random

    from .modular import mod_u64_const

    hi = random.bits(key, shape=shape, dtype=jnp.uint32)
    k2 = random.fold_in(key, 1)
    lo = random.bits(k2, shape=shape, dtype=jnp.uint32)
    return mod_u64_const(hi, lo, m).astype(jnp.int64)


def uniform_bits_device(key, shape, nbits: int):
    """Uniform draws over ``[0, 2**nbits)`` via masked random bits.

    Exact (power-of-two range — zero modulo bias): one draw and a mask,
    where :func:`uniform_mod_device` takes two draws and a reduction by the
    modulus's reciprocal. ``bench.py`` uses this for synthetic participant
    data with ``nbits = p.bit_length() - 1``, a sub-range of the field that
    exercises identical arithmetic.
    Simulation only — protocol-plane randomness is host CSPRNG rejection
    sampling (``uniform_mod_host``), where full-range uniformity is a
    privacy requirement, not a convenience.
    """
    import jax.numpy as jnp
    from jax import random

    if not (0 < nbits <= 62):
        raise ValueError(f"nbits out of range: {nbits}")
    dtype = jnp.uint32 if nbits <= 32 else jnp.uint64
    u = random.bits(key, shape=shape, dtype=dtype)
    return (u & dtype((1 << nbits) - 1)).astype(jnp.int64)


def uniform_bits_device_pair(key, shape, nbits: int):
    """``uniform_bits_device`` for ``32 <= nbits <= 62``, returned as a
    ``(hi, lo)`` pair of uint32 tensors with value ``hi·2³² + lo``
    (``nbits == 32`` yields an all-zero hi half — still exact).

    The value never exists as an int64 on device: wide (61-bit) hot paths
    consume the halves directly in native 32-bit lanes
    (``sumfirst.value_limb_sums_chunk_pair``), skipping the emulated
    64-bit ops that otherwise dominate. Simulation only, like the other
    masked-bits draws."""
    import jax.numpy as jnp
    from jax import random

    if not (32 <= nbits <= 62):
        raise ValueError(f"pair draw needs 32 <= nbits <= 62, got {nbits}")
    hi = random.bits(key, shape=shape, dtype=jnp.uint32) & jnp.uint32(
        (1 << (nbits - 32)) - 1
    )
    lo = random.bits(random.fold_in(key, 1), shape=shape, dtype=jnp.uint32)
    return hi, lo


def uniform_bits_device_narrow(key, shape, nbits: int):
    """``uniform_bits_device`` for ``nbits <= 31``, kept int32.

    Same bits as the wide variant for the same key (uint32 draw, masked),
    but never widened — feeds the narrow (int32) hot paths where emulated
    64-bit lanes would halve throughput (parallel/sumfirst.py)."""
    import jax.numpy as jnp
    from jax import random

    if not (0 < nbits <= 31):
        raise ValueError(f"narrow draw needs nbits <= 31, got {nbits}")
    u = random.bits(key, shape=shape, dtype=jnp.uint32)
    return (u & jnp.uint32((1 << nbits) - 1)).astype(jnp.int32)
