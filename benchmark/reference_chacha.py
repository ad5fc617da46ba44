"""The plain reference of the ChaCha mask expansion: what the mask of a seed
is, and what the masks of many seeds sum to mod p.

Independent of the code under test: imports nothing from ``sda_tpu``, runs no
kernel, and picks the accepted draws by a boolean index, row by row, where the
program moves them by a prefix sum. The specification is the upstream's
``ChaChaRng::from_seed(&seed)`` + ``gen_range(0_i64, p)`` of rand 0.3
(``client/src/crypto/masking/chacha.rs:29-77``):

* the key is the seed's u32 words zero-padded to eight; the nonce is zero,
  the 64-bit block counter starts at 0 (djb's ChaCha20, twenty rounds), and
  all sixteen output words of a block are consumed in order;
* a draw is ``next_u64 = (w[2i] << 32) | w[2i+1]``; it is accepted where it is
  below ``zone = u64::MAX - u64::MAX % p`` and then reduced mod p; the mask is
  the first ``dim`` accepted draws.

The keystream is plain ``jax.numpy`` on whole arrays, so that on the chip the
blocks of a thousand seeds are made on the device (:func:`mask_sum`, a block
of seeds at a time); the selection, the remainder and the sum are ``numpy``'s
on the host (a sort on the device would do the selection too, and takes the
chip's compiler 37 s: PERF.md section 6, PR 32). The sum of the masks is taken
in 32-bit halves, exact in int64 below 2^31 seeds, and widened once, in
python integers.
"""

from __future__ import annotations

import numpy as np

U64_MAX = (1 << 64) - 1
CONSTANTS = (0x61707865, 0x3320646E, 0x79622D32, 0x6B206574)  # "expand 32-byte k"
QUARTER_ROUNDS = (
    (0, 4, 8, 12), (1, 5, 9, 13), (2, 6, 10, 14), (3, 7, 11, 15),  # columns
    (0, 5, 10, 15), (1, 6, 11, 12), (2, 7, 8, 13), (3, 4, 9, 14),  # diagonals
)


def zone(modulus: int) -> int:
    """rand 0.3's ``Range``: a u64 draw is accepted below this. Not the
    textbook ``2^64 - 2^64 % p``: where p divides 2^64 the top p values are
    still rejected."""
    return U64_MAX - U64_MAX % int(modulus)


def window_pairs(dim: int, modulus: int) -> int:
    """Draws to make so that a row holds ``dim`` accepted ones: the expected
    number and ten standard deviations more. Any window that holds them gives
    the same mask; :func:`expand` says where one did not."""
    q = (U64_MAX - zone(modulus) + 1) / float(1 << 64)  # a draw is rejected
    expected = dim / (1.0 - q)
    return int(expected + 10.0 * (expected * q) ** 0.5 / (1.0 - q)) + 16


def keystream(seeds, n_blocks: int):
    """``(P, w <= 8)`` uint32 seeds -> ``(P, n_blocks * 16)`` uint32: each
    seed's first ``n_blocks`` ChaCha20 blocks, word after word. Traceable."""
    import jax.numpy as jnp
    from jax import lax

    seeds = jnp.asarray(seeds, jnp.uint32)
    rows, words = seeds.shape
    shape = (rows, n_blocks)
    if n_blocks >= 1 << 32:
        raise ValueError("the block counter's high word is taken as zero")
    state = [jnp.full(shape, c, jnp.uint32) for c in CONSTANTS]
    state += [
        jnp.broadcast_to(seeds[:, i, None], shape) if i < words else jnp.zeros(shape, jnp.uint32)
        for i in range(8)
    ]
    state += [
        jnp.broadcast_to(jnp.arange(n_blocks, dtype=jnp.uint32), shape),  # counter, low word
        jnp.zeros(shape, jnp.uint32),  # counter, high word
        jnp.zeros(shape, jnp.uint32), jnp.zeros(shape, jnp.uint32),  # the nonce
    ]

    def rotl(x, r):
        return (x << jnp.uint32(r)) | (x >> jnp.uint32(32 - r))

    def double_round(_i, x):
        x = list(x)
        for a, b, c, d in QUARTER_ROUNDS:
            x[a] = x[a] + x[b]
            x[d] = rotl(x[d] ^ x[a], 16)
            x[c] = x[c] + x[d]
            x[b] = rotl(x[b] ^ x[c], 12)
            x[a] = x[a] + x[b]
            x[d] = rotl(x[d] ^ x[a], 8)
            x[c] = x[c] + x[d]
            x[b] = rotl(x[b] ^ x[c], 7)
        return tuple(x)

    x = lax.fori_loop(0, 10, double_round, tuple(state))
    words_out = jnp.stack([xi + si for xi, si in zip(x, state)], axis=-1)
    return words_out.reshape(rows, n_blocks * 16)


_KEYSTREAM_JIT = None


def draws(seeds, pairs: int) -> np.ndarray:
    """``(P, w)`` uint32 seeds -> ``(P, >= pairs)`` uint64 numpy: every
    seed's u64 draws in stream order, whole blocks of eight, the keystream
    made wherever ``jax.numpy`` computes."""
    global _KEYSTREAM_JIT
    if _KEYSTREAM_JIT is None:
        import jax

        _KEYSTREAM_JIT = jax.jit(keystream, static_argnums=1)
    words = np.asarray(_KEYSTREAM_JIT(np.asarray(seeds, np.uint32), -(-pairs * 2 // 16)))
    return (words[:, 0::2].astype(np.uint64) << np.uint64(32)) | words[:, 1::2].astype(np.uint64)


def expand(seeds, dim: int, modulus: int, pairs: int | None = None):
    """``(P, w)`` uint32 seeds -> ``((P, dim) int64 masks, (P,) accepted
    draws of the window)``, numpy. A row whose count is under ``dim`` has no
    mask from this window (its tail is zeros); :func:`masks` widens the
    window until none is left."""
    stream = draws(seeds, window_pairs(dim, modulus) if pairs is None else pairs)
    accepted = stream < np.uint64(zone(modulus))
    first = np.zeros((stream.shape[0], dim), np.uint64)
    for row, (row_draws, row_accepted) in enumerate(zip(stream, accepted)):
        kept = row_draws[row_accepted][:dim]
        first[row, : kept.size] = kept
    return (first % np.uint64(modulus)).astype(np.int64), accepted.sum(axis=1)


def masks(seeds, dim: int, modulus: int) -> np.ndarray:
    """``(P, dim)`` int64 numpy: every seed's mask, the window doubled for as
    long as a row came short."""
    pairs = window_pairs(dim, modulus)
    while True:
        out, counts = expand(seeds, dim, modulus, pairs)
        if int(np.min(counts, initial=dim)) >= dim:
            return out
        pairs *= 2


def half_sums(block: np.ndarray) -> np.ndarray:
    """``(rows, dim)`` masks below 2^63 -> ``(2, dim)`` int64: the sums down
    the rows of their low and high 32-bit halves."""
    block = block.astype(np.int64, copy=False)
    return np.stack([
        (block & np.int64(0xFFFFFFFF)).sum(axis=0), (block >> np.int64(32)).sum(axis=0)
    ])


def mask_sum(seeds, dim: int, modulus: int, block: int = 50) -> np.ndarray:
    """``(dim,)`` int64: the sum mod p of the masks of ``(P, w)`` seeds,
    ``block`` seeds' keystream made at a time."""
    seeds = np.asarray(seeds, np.uint32)
    if seeds.shape[0] >= 1 << 31:
        raise ValueError("half sums are exact in int64 below 2^31 seeds")
    halves = np.zeros((2, dim), dtype=np.int64)
    for start in range(0, seeds.shape[0], block):
        halves += half_sums(masks(seeds[start : start + block], dim, modulus))
    low, high = halves.astype(object)
    return ((low + high * (1 << 32)) % int(modulus)).astype(np.int64)
