"""Deterministic ChaCha20 keystream expansion for seed-compressed masking.

The ChaCha masking scheme uploads only a small seed; participant (mask) and
recipient (re-expansion) must expand it to a dim-length mask *bit-identically*
or unmasking silently corrupts the result (SURVEY.md hard part #4; reference:
client/src/crypto/masking/chacha.rs).

Expansion spec — BIT-EXACT to the reference's rand-0.3
``ChaChaRng::from_seed(&seed)`` + per-element ``gen_range(0_i64, m)``
(client/src/crypto/masking/chacha.rs:36-39, 56-77; client/Cargo.toml:18
pins rand "0.3"), so a mixed deployment (reference participant, this
recipient — or vice versa) unmasks correctly:

- Key: the seed's u32 words zero-padded to 8 words (256-bit key) — rand
  0.3 ``reseed`` zips the seed into a zeroed key ("the PRG will use at
  most 256 bits", chacha.rs:10).
- Stream: classic djb ChaCha20, zero nonce, block counter starting at 0,
  all 16 output words consumed in order — rand 0.3's ChaChaRng layout.
  (rand 0.3 carries a 128-bit counter over words 12-15 where this
  implementation carries 64 bits over words 12-13; they diverge only
  after 2^64 blocks ≈ 10^21 draws, unreachable at any real dimension.)
- Draws: ``gen_range(0, m)`` draws ``next_u64`` = two consecutive u32
  words as ``(w[2i] << 32) | w[2i+1]`` (rand 0.3's default ``next_u64``
  takes the high half first), REJECTS values >= zone, and reduces the
  accepted value mod m. zone = ``u64::MAX - u64::MAX % m`` exactly as
  rand 0.3's ``Range::construct_range`` computes it — note this differs
  from the textbook ``2^64 - 2^64 % m`` precisely when m divides 2^64
  (then rand still rejects the top m values; a spec using the textbook
  zone would silently diverge from the reference for power-of-two
  moduli).

Implemented with vectorized numpy uint32 (wrapping arithmetic); block-level
parallel so a 100K-dim expansion is ~3K independent blocks — the same
formulation a Pallas port would use.
"""

from __future__ import annotations

import numpy as np


def rand03_zone(modulus: int) -> int:
    """rand 0.3's rejection zone for ``gen_range(0, modulus)`` on u64
    draws: accept v < zone, zone = u64::MAX - u64::MAX % range
    (rand-0.3 distributions/range.rs, integer_impl!). The single
    definition every backend (numpy here, jnp/Pallas in
    chacha_pallas.py, C in native/_sdanative.c — asserted equal in
    tests) must agree with."""
    if modulus <= 0:
        raise ValueError("modulus must be positive")
    if modulus > (1 << 63):
        # masks are int64 and gen_range draws i64 — above 2^63 the
        # reduced draws wrap negative in int64, silently corrupting the
        # aggregate; no legal scheme modulus (i64) can reach here
        raise ValueError(f"modulus {modulus} exceeds the int64 mask range")
    u64_max = (1 << 64) - 1
    return u64_max - (u64_max % modulus)

_CONSTANTS = np.array([0x61707865, 0x3320646E, 0x79622D32, 0x6B206574], dtype=np.uint32)

_QUARTER_ROUNDS = [
    # column rounds
    (0, 4, 8, 12),
    (1, 5, 9, 13),
    (2, 6, 10, 14),
    (3, 7, 11, 15),
    # diagonal rounds
    (0, 5, 10, 15),
    (1, 6, 11, 12),
    (2, 7, 8, 13),
    (3, 4, 9, 14),
]


def _rotl(x: np.ndarray, r: int) -> np.ndarray:
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def chacha_blocks(key_words: np.ndarray, first_counter: int, n_blocks: int) -> np.ndarray:
    """n_blocks ChaCha20 blocks -> (n_blocks, 16) uint32 keystream words."""
    key = np.zeros(8, dtype=np.uint32)
    key[: len(key_words)] = np.asarray(key_words, dtype=np.uint32)
    counters = np.arange(first_counter, first_counter + n_blocks, dtype=np.uint64)
    state = np.zeros((n_blocks, 16), dtype=np.uint32)
    state[:, 0:4] = _CONSTANTS
    state[:, 4:12] = key
    state[:, 12] = (counters & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    state[:, 13] = (counters >> np.uint64(32)).astype(np.uint32)
    # words 14-15: zero nonce

    x = state.copy()
    with np.errstate(over="ignore"):
        for _ in range(10):  # 20 rounds = 10 double rounds
            for (a, b, c, d) in _QUARTER_ROUNDS:
                x[:, a] += x[:, b]
                x[:, d] = _rotl(x[:, d] ^ x[:, a], 16)
                x[:, c] += x[:, d]
                x[:, b] = _rotl(x[:, b] ^ x[:, c], 12)
                x[:, a] += x[:, b]
                x[:, d] = _rotl(x[:, d] ^ x[:, a], 8)
                x[:, c] += x[:, d]
                x[:, b] = _rotl(x[:, b] ^ x[:, c], 7)
        x += state
    return x


def chacha_state_jnp(key_words, first_counter: int, n_blocks: int):
    """Initial ChaCha20 states: (n_blocks, 16) uint32 (pre-round input).

    What the jnp round loop starts from. The Pallas kernel
    (chacha_pallas.py) makes the same sixteen words in registers from the key
    and a block index and reads no state; the tests hold both to the numpy
    blocks above. ``key_words`` may be a traced (8,) uint32 array.
    """
    from .jaxcfg import ensure_x64

    ensure_x64()
    import jax.numpy as jnp

    counters = jnp.arange(first_counter, first_counter + n_blocks, dtype=jnp.uint64)
    state = jnp.zeros((n_blocks, 16), dtype=jnp.uint32)
    state = state.at[:, 0:4].set(jnp.asarray(_CONSTANTS))
    key = jnp.zeros(8, dtype=jnp.uint32).at[: len(key_words)].set(
        jnp.asarray(key_words, dtype=jnp.uint32)
    )
    state = state.at[:, 4:12].set(key)
    state = state.at[:, 12].set((counters & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32))
    state = state.at[:, 13].set((counters >> jnp.uint64(32)).astype(jnp.uint32))
    return state


def apply_rounds_jnp(cols):
    """The 20 ChaCha rounds on a 16-list of uint32 jnp arrays (no
    feed-forward). Single source of the round body for every traced path —
    the jnp twin and the Pallas kernel both call this; only the numpy host
    implementation above stays independent, as the cross-check reference."""
    import jax.numpy as jnp

    def rotl(x, r):
        return (x << jnp.uint32(r)) | (x >> jnp.uint32(32 - r))

    for _ in range(10):  # 20 rounds = 10 double rounds
        for (a, b, c, d) in _QUARTER_ROUNDS:
            cols[a] = cols[a] + cols[b]
            cols[d] = rotl(cols[d] ^ cols[a], 16)
            cols[c] = cols[c] + cols[d]
            cols[b] = rotl(cols[b] ^ cols[c], 12)
            cols[a] = cols[a] + cols[b]
            cols[d] = rotl(cols[d] ^ cols[a], 8)
            cols[c] = cols[c] + cols[d]
            cols[b] = rotl(cols[b] ^ cols[c], 7)
    return cols


def chacha_rounds_jnp(state):
    """20 ChaCha rounds + feed-forward on ``(..., 16)`` uint32 states."""
    import jax.numpy as jnp

    cols = apply_rounds_jnp([state[..., i] for i in range(16)])
    return jnp.stack(cols, axis=-1) + state


def chacha_blocks_jnp(key_words, first_counter: int, n_blocks: int):
    """Device twin of ``chacha_blocks``: (n_blocks, 16) uint32 keystream.

    Bit-identical to the numpy implementation (the whole point — mask and
    re-expansion may run on different backends; see module doc). Vectorized
    over blocks, so a 100K-dim expansion is ~3K parallel block lanes on the
    VPU. ``key_words`` may be a traced (8,) uint32 array.
    """
    from .jaxcfg import ensure_x64

    ensure_x64()
    return chacha_rounds_jnp(chacha_state_jnp(key_words, first_counter, n_blocks))


def expand_seed_jnp(seed_words, dim: int, modulus: int):
    """Device twin of ``expand_seed``: (dim,) int64 mask in [0, modulus).

    Eager-mode (the window guard reads a device scalar): delegates to the
    batched expansion (chacha_pallas.expand_seeds_batch) with P=1 — same
    zone rejection and draw order as the host path, with a q-scaled
    overgenerated window and a ``SlackExhausted`` guard instead of wrong
    bits. Bit-identical to ``expand_seed`` (asserted at test time).
    """
    import jax.numpy as jnp

    from .chacha_pallas import expand_seeds_batch

    seeds = jnp.asarray(seed_words, dtype=jnp.uint32)[None, :]
    return expand_seeds_batch(seeds, dim, modulus, backend="jnp")[0]


def expand_seed(seed_words, dim: int, modulus: int) -> np.ndarray:
    """Expand seed u32 words to a dim-length int64 mask in [0, modulus).

    Bit-exact to the reference's rand-0.3 expansion (module doc)."""
    zone = rand03_zone(modulus)
    # rejection probability q = (u64::MAX % m + 1) / 2^64 — up to 1/2 at
    # the maximum m = 2^63 — so size each refill from the actual q
    q = ((1 << 64) - zone) / float(1 << 64)
    out = np.empty(0, dtype=np.int64)
    counter = 0
    while len(out) < dim:
        need = dim - len(out)
        need_pairs = int(need / (1.0 - q)) + 8
        n_blocks = (need_pairs * 2 + 15) // 16
        words = chacha_blocks(seed_words, counter, n_blocks).reshape(-1)
        counter += n_blocks
        u64 = (words[0::2].astype(np.uint64) << np.uint64(32)) | words[1::2].astype(np.uint64)
        u64 = u64[u64 < np.uint64(zone)]
        out = np.concatenate([out, (u64 % np.uint64(modulus)).astype(np.int64)])
    return out[:dim]
