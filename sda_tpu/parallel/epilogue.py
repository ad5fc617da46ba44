"""The device road of a sum-first round's epilogue and reveal: the clerk sums
from the accumulator, and the aggregate from the clerk sums of a subset of
clerks, both computed where the accumulator lies.

The host road (``sumfirst.clerk_sums_from_limb_acc``, then
``ops.shamir.reconstruct_clerk_sums_host``) fetches the int64 accumulator,
which leaves the chip at 0.21 GB/s, and works in numpy. That is the cheaper
road while the accumulator is small: the eight cells of the 10⁵-wide cohorts
hold 2.2e5 to 3.2e6 B. Once it is as large as a chunk of the input (a few fat
rows of 10⁸ coordinates: 2.5e9 B) the host road is the round, and this one
keeps it on the chip. :data:`DEVICE_ROAD_BYTES` is where the round driver
(``round.FoldRound``) switches, by the accumulator's size.

Both programs work on uint32 words, the chip's lanes, with the 61-bit field
multiply ``ops.modular.mulmod_shoup`` and the reduction
``ops.modular.mod_u64_const``; no 64-bit multiply or division is asked of the
chip. Both hand back the same integers as the host road, bit for bit.

That arithmetic runs on ``(rows, 128)`` tiles: every operand of a product is
a block of batches as rows of 128, so a vector register's eight sublanes of
128 lanes are all filled. A product is about 130 lane operations (six 16-bit
schoolbook multiplies, their carries, a conditional subtract) and reads and
writes a few words, so the vector unit, not the memory, bounds both programs;
an operand laid out as one row of a block's batches, ``(1, B)``, is tiled one
row of 128 to a register and leaves seven sublanes in eight idle (on a TPU
v5e the two programs took 0.92 s a round that way at the silo cohort's 2.2e7
batches, 0.17 s on whole tiles). So the batches are padded to whole rows of
128 (:func:`_blocking`), every block starts on a whole row, and each clerk's
sums and each secret are ``(rows, 128)``.

* :func:`clerk_sums` takes the flat accumulator of
  ``sumfirst.value_limb_sums_flat`` (or the batched one of
  ``value_limb_sums_chunk``, laid flat first) and returns the ``(n, B)`` int64
  canonical clerk sums. Under ``fabric.epilogue/recombine`` every column's
  limb sums become one value mod p: the low limb by ``mod_u64_const``, the
  high one times 2³² by ``mulmod_shoup``, the two added mod p. Under
  ``fabric.epilogue/share_matmul`` the values are laid out as ``K`` planes of
  the ``B`` batches (:func:`_planes`: transpositions, no gather) and every
  clerk's sum is ``Σ_i S[j, i]·v_i``, seven products below p added in 64 bits
  (under 7p < 2⁶⁴) and reduced once.
* :func:`reveal` takes those clerk sums, the rows of the clerks revealed
  from and their Lagrange matrix as operands (one program for every subset),
  and returns the ``(dim,)`` aggregate as one ``(2·dim,)`` uint32 array, each
  coordinate's low word then its high word: a 32-bit array crosses to the
  host at 3.3 GB/s where an int64 one crosses at 0.21 (measured on a TPU
  v5e), and these words are the aggregate's little-endian int64 bytes. Device
  scope ``fabric.reveal/reconstruct``; the crossing is the host's
  (:func:`fetch_aggregate`: span ``fabric.reveal.fetch``, counter
  ``sda_fabric_reveal_fetch_bytes_total``), and the aggregate is a view of
  the fetched words.
"""

from __future__ import annotations

import sys

import numpy as np

from .. import telemetry
from ..ops import shamir
from ..ops.jaxcfg import ensure_x64
from ..ops.modular import add64, mod_u64_const, mulmod_shoup, shoup_words

#: accumulator bytes from which the round driver takes the device road. The
#: host road costs a round ≈ 10.6 ns an accumulator byte: the fetch at 0.21
#: GB/s (4.8 ns, measured on a TPU v5e) and the numpy epilogue and
#: reconstruct, 0.0132 s for c5-sumfirst's 2.24e6 B on the v5e's host
#: (5.9 ns). The device road's two programs are bound by the vector unit's
#: field arithmetic, not by moving the accumulator (5.96e9 B at 2.45e9 B of
#: accumulator, 7.3 ms at the memory's peak): on a TPU v5e they take 0.17 s a
#: round at 2.45e9 B (≈ 0.07 ns an accumulator byte; 0.92 s while their
#: operands were one row of a block), and they compile in set-up, a few
#: seconds. At 2**28 B the host road would take 2.8 s every round, so the
#: device road pays that back in its first round. The eight cells of the
#: 10⁵-wide cohorts (≤ 3.2e6 B) are eighty times under it and keep the road
#: their numbers were measured on; the silo cohort's 2.45e9 B is nine times
#: over it.
DEVICE_ROAD_BYTES = 1 << 28

#: a tile's lanes: the planes are laid out 128 batches at a time
_LANES = 128

#: batches a block of the programs' loop: both programs loop over the batches
#: block by block, so that what they keep beside their operands and result is
#: a block's (≈ 0.6e9 B at 2**21 batches) and not the whole accumulator's
#: (6.7e9 B at 2.2e7 batches, which no chip holds beside the rows)
BLOCK_BATCHES = 1 << 21


def device_road(acc_bytes: int) -> bool:
    """Whether an accumulator of ``acc_bytes`` takes the device road."""
    return acc_bytes >= DEVICE_ROAD_BYTES


def count_road(road: str) -> None:
    """One epilogue of a round, by the road it took (``host`` or
    ``device``)."""
    telemetry.counter(
        "sda_fabric_epilogue_roads_total",
        "sum-first epilogues run, by road (host | device)",
        road=road,
    ).inc()


def _words(x):
    """int64 (non-negative) -> ``(hi, lo)`` uint32 words."""
    import jax.numpy as jnp

    return (x >> jnp.int64(32)).astype(jnp.uint32), x.astype(jnp.uint32)


def _split(u):
    """uint64 -> ``(hi, lo)`` uint32 words."""
    import jax.numpy as jnp

    return (u >> jnp.uint64(32)).astype(jnp.uint32), u.astype(jnp.uint32)


def _planes(x, w: int):
    """``(R, 128·w)`` rows of 128 batches, element ``l·w + i`` of a row batch
    ``l``'s part ``i``, -> ``(w, R, 128)``: part ``i`` of every batch in
    whole tiles. A reshape to ``(R·128, w)`` would keep ``w`` in a tile's 128
    lanes (25 times the bytes at w = 5); two transpositions of large tiles
    make the same move."""
    rows = x.shape[0]
    t = x.T  # (128·w, R): row l·w + i
    return t.reshape(_LANES, w, rows).transpose(1, 2, 0)


def _interleave(planes):
    """``(w, R, 128)`` -> ``(R·128·w,)``, :func:`_planes` undone and laid
    flat."""
    w, rows, _ = planes.shape
    t = planes.transpose(2, 0, 1).reshape(_LANES * w, rows).T  # (R, 128·w)
    return t.reshape(rows * _LANES * w)


def _sum_mod(products, p: int):
    """``Σ products mod p``: ``products`` a list of ``(hi, lo)`` words, each
    below p, their sum below 2⁶⁴; added in 64 bits, reduced once."""
    hi, lo = products[0]
    for b_hi, b_lo in products[1:]:
        hi, lo = add64(hi, lo, b_hi, b_lo)
    return _split(mod_u64_const(hi, lo, p))


def word_limb_acc(acc, plan):
    """The batched ``(L, B, K)`` int64 accumulator laid flat as the ``(L, 2,
    N)`` uint32 words ``sumfirst.value_limb_sums_flat`` hands on; a word
    accumulator as it is."""
    import jax.numpy as jnp

    if acc.ndim == 3 and acc.dtype == jnp.uint32:
        return acc
    k, L = plan.input_size, acc.shape[0]
    flat = jnp.concatenate([acc[..., :k].reshape(L, -1), acc[..., k:].reshape(L, -1)], axis=-1)
    return jnp.stack(_words(flat), axis=1)


def _check_fits(plan) -> None:
    p, K = plan.modulus, plan.input_size + plan.rand_size
    if plan.share_matrix is None:
        raise ValueError("the device epilogue needs a packed share matrix")
    if p >= 1 << 62 or max(K, plan.share_count) * p >= 1 << 64:
        raise ValueError(f"a sum of {max(K, plan.share_count)} products mod {p} leaves 64 bits")


def _blocking(B: int) -> tuple:
    """``(rows, size, count)``: the ``B`` batches padded to ``rows`` rows of
    128, and the blocks of ``size`` rows the programs loop over, ``count`` of
    them; block ``j`` starts at row ``min(j·size, rows − size)`` (the last one
    overlaps the one before where ``size`` does not divide ``rows``: its
    batches are computed twice, to the same values)."""
    rows = -(-B // _LANES)
    size = min(rows, max(1, BLOCK_BATCHES // _LANES))
    return rows, size, -(-rows // size)


def _block_start(j, size: int, rows: int):
    import jax.numpy as jnp

    return jnp.minimum(j * size, rows - size).astype(jnp.int32)


def clerk_sums(word_acc, plan):
    """``(L, 2, B·k + B·t)`` limb sums as words
    (``sumfirst.value_limb_sums_flat``) -> ``(n, B)`` int64 canonical clerk
    sums, the host road's bit for bit, block by block of
    :data:`BLOCK_BATCHES` batches. Traceable; :func:`clerk_sums_program` is
    its jitted program."""
    ensure_x64()
    import jax
    import jax.numpy as jnp
    from jax import lax

    _check_fits(plan)
    p, k, t, B, n = plan.modulus, plan.input_size, plan.rand_size, plan.n_batches, plan.share_count
    S = plan.share_matrix
    L = word_acc.shape[0]
    rows, size, count = _blocking(B)
    # the last block's random part reads whole rows past the B batches
    word_acc = jnp.pad(word_acc, ((0, 0), (0, 0), (0, (rows * _LANES - B) * t)))

    def columns(row, offset, w):
        """The block's batches of one part as ``w`` planes of values mod p,
        ``(size, 128)`` each."""
        start = offset + row * (_LANES * w)
        part = lax.dynamic_slice_in_dim(word_acc, start, size * _LANES * w, axis=2)
        with jax.named_scope("fabric.epilogue/recombine"):
            part = part.reshape(L, 2, size, _LANES * w)  # rows of 128 batches
            v = _split(mod_u64_const(part[0, 0], part[0, 1], p))
            if L == 2:
                # the high limb counts 2³² times: its sum times 2³² mod p
                high = mulmod_shoup(part[1, 0], part[1, 1], shoup_words((1 << 32) % p, p), p)
                v = _sum_mod([v, high], p)
        with jax.named_scope("fabric.epilogue/share_matmul"):
            planes = [_planes(word, w) for word in v]  # (w, size, 128) each
            return [(planes[0][i], planes[1][i]) for i in range(w)]

    def block(j, out):
        row = _block_start(j, size, rows)
        values = columns(row, 0, k) + columns(row, B * k, t)
        with jax.named_scope("fabric.epilogue/share_matmul"):
            sums = []
            for j_clerk in range(n):
                products = [
                    mulmod_shoup(*values[i], shoup_words(int(S[j_clerk, i]) % p, p), p)
                    for i in range(k + t)
                ]
                sums.append(_sum_mod(products, p))
            return tuple(
                lax.dynamic_update_slice_in_dim(o, jnp.stack([s[w] for s in sums]), row, axis=1)
                for w, o in enumerate(out)
            )

    zeros = jnp.zeros((n, rows, _LANES), jnp.uint32)
    hi, lo = lax.fori_loop(0, count, block, (zeros, zeros))
    with jax.named_scope("fabric.epilogue/share_matmul"):
        hi, lo = (word.reshape(n, rows * _LANES)[:, :B] for word in (hi, lo))
        return (hi.astype(jnp.int64) << jnp.int64(32)) | lo.astype(jnp.int64)


def lagrange_operand(scheme, clerks) -> np.ndarray:
    """The ``(4, k, R)`` uint32 operand of :func:`reveal`: the Lagrange
    matrix of the 0-based ``clerks`` (``shamir.reconstruction_matrix``) as
    :func:`ops.modular.shoup_words` gives each entry."""
    p = scheme.prime_modulus
    matrix = shamir.reconstruction_matrix(scheme, list(clerks))  # (k, R)
    words = [[shoup_words(int(c) % p, p) for c in row] for row in matrix]
    return np.asarray(words, dtype=np.uint32).transpose(2, 0, 1).copy()


def reveal(clerk_sums_, rows, lagrange, plan):
    """The ``(dim,)`` aggregate from the ``(n, B)`` clerk sums of
    :func:`clerk_sums`: ``rows`` the ``(R,)`` int32 clerks revealed from,
    ``lagrange`` their :func:`lagrange_operand`. Returns its ``(2·dim,)``
    uint32 words, coordinate ``c``'s low word at ``2c`` and its high word at
    ``2c + 1``, block by block of :data:`BLOCK_BATCHES` batches. Traceable;
    :func:`reveal_program` is its jitted program."""
    ensure_x64()
    import jax
    import jax.numpy as jnp
    from jax import lax

    _check_fits(plan)
    p, k, B, n = plan.modulus, plan.input_size, plan.n_batches, plan.share_count
    R = rows.shape[0]
    if R * p >= 1 << 64:
        raise ValueError(f"a sum of {R} products mod {p} leaves 64 bits")
    tile_rows, size, count = _blocking(B)
    with jax.named_scope("fabric.reveal/reconstruct"):
        # every clerk's sums as rows of 128 batches, once
        tiled = jnp.pad(clerk_sums_, ((0, 0), (0, tile_rows * _LANES - B)))
        tiled = _words(tiled.reshape(n, tile_rows, _LANES))

    def block(j, out):
        row = _block_start(j, size, tile_rows)
        with jax.named_scope("fabric.reveal/reconstruct"):
            at = [(r.astype(jnp.int32), row, jnp.int32(0)) for r in rows]
            shares = [
                tuple(lax.dynamic_slice(word, start, (1, size, _LANES))[0] for word in tiled)
                for start in at
            ]
            words = []
            for i in range(k):
                products = [mulmod_shoup(*shares[r], tuple(lagrange[:, i, r]), p) for r in range(R)]
                hi, lo = _sum_mod(products, p)
                words += [lo, hi]
            # 2k planes, secret-major and low word first: interleaved, a
            # coordinate's two words lie side by side, as its int64 bytes
            return lax.dynamic_update_slice_in_dim(
                out, _interleave(jnp.stack(words)), row * (_LANES * 2 * k), axis=0
            )

    out = jnp.zeros((tile_rows * _LANES * 2 * k,), jnp.uint32)
    return lax.fori_loop(0, count, block, out)[: 2 * plan.dim]


def clerk_sums_program(plan):
    """The jitted ``device_clerk_sums(word_acc) -> (n, B)`` of ``plan``."""
    import jax

    def device_clerk_sums(word_acc):
        return clerk_sums(word_acc, plan)

    return jax.jit(device_clerk_sums)


def reveal_program(plan):
    """The jitted ``device_reveal(clerk_sums, rows, lagrange) -> words`` of
    ``plan``: one program for every subset of clerks."""
    import jax

    def device_reveal(clerk_sums_, rows, lagrange):
        return reveal(clerk_sums_, rows, lagrange, plan)

    return jax.jit(device_reveal)


class _Taken:
    """A fetched host array handed on writable, over the same memory: JAX
    marks the host copy of a device array read-only, and the aggregate is its
    caller's to keep and change, as the host road's is. It holds the fetched
    array, which holds the memory."""

    def __init__(self, host: np.ndarray):
        self.host = host
        self.__array_interface__ = {**host.__array_interface__, "data": (host.ctypes.data, False)}


def fetch_aggregate(words) -> np.ndarray:
    """The ``(dim,)`` int64 aggregate on the host from the ``(2·dim,)``
    uint32 device words of :func:`reveal`, which it takes: the crossing under
    the span ``fabric.reveal.fetch`` (``bytes``), counted in
    ``sda_fabric_reveal_fetch_bytes_total``, then the device words deleted,
    so that the host copy has no other reader. On a little-endian host the
    words are the aggregate's own int64 bytes, and it is their view: no pass
    over the values, no new array; elsewhere the words are joined."""
    import jax

    nbytes = int(words.nbytes)
    with telemetry.span("fabric.reveal.fetch", bytes=nbytes):
        host = jax.device_get(words)
    words.delete()
    telemetry.counter(
        "sda_fabric_reveal_fetch_bytes_total",
        "bytes of revealed aggregates fetched from the device",
    ).inc(nbytes)
    if sys.byteorder == "little":
        return np.asarray(_Taken(host)).view(np.int64)
    out = host[1::2].astype(np.int64)
    out <<= 32
    out |= host[0::2]
    return out
