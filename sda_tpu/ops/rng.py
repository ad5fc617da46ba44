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
