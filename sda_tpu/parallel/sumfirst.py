"""Sum-first clerk sums: ``share(Σ_c v_c) = Σ_c share(v_c)`` (linearity).

Packed-Shamir share generation is a fixed linear map ``v ↦ v @ S`` over the
prime field (ops/shamir.py), and the clerk's job is the *sum* of all
participants' shares (reference: client/src/clerk.rs:85-86,
client/src/crypto/sharing/combiner.rs:16-30). Matmul and participant-sum
commute, so when the fabric's goal is the clerk sums themselves — the
co-hosted/simulated-participant setting the TPU aggregation fabric exists
for (SURVEY.md §2.3) — the hot loop over the big ``(participants, dim)``
tensor reduces to one streaming integer reduction, and the share matmul
runs once on the tiny ``(B, K)`` participant-sum. Bit-exact: both orders
compute the same field elements.

Do NOT use this path when individual participants' shares must exist —
e.g. to be sealed per clerk for transport (the real multi-party protocol
plane, client/participate.py); that's ``engine.share_participants``.

Overflow discipline: the reduction is carried as *exact integer* sums in
base-2³² limb space — no mod ops touch the big tensor at all. Canonical
values ``v < p < 2⁶²`` split into ``lo = v & (2³²−1)`` and ``hi = v ≫ 32``;
limb sums over ``C_total`` participants are bounded by ``C_total · (2³²−1)``,
so the int64 *accumulator* is exact for up to 2³¹ participants (2048× the 1M
north star). For ``p ≤ 2³¹`` a single limb suffices.

Which road a chunk's limb sums take (``limb_sum_road``: the modulus' limb
count and the chunk's row count, nothing else) — the chip's lanes are 32
bits wide, and a 64-bit tensor costs it a re-laying and carries besides:

- ``int32`` — one limb, at most ``MAX_NARROW_CHUNK`` rows: the values as
  they are through ``exact_sum_narrow`` (16-bit quarters summed in int32
  lanes, exact while ``C · 65 535 < 2³¹``).
- ``halves32`` — two limbs, at most ``MAX_NARROW_CHUNK`` rows: the low and
  the high 32-bit word of each value as uint32, each through
  ``exact_sum_narrow`` under the same bound; no 64-bit tensor of C rows is
  reduced. The share randomness is drawn flat, ``(C, B·t)``, the same
  values in a full tile, and each value once.
- ``int64`` — a larger chunk, at either width: plain int64 sums, exact as
  the accumulator is.

Only the reduced ``(B, cols)`` rows widen to int64; all three hand on the
same ``(L, B, K)`` accumulator, element for element.

- ``rows`` — the flat entry's (``value_limb_sums_flat``), for a chunk of a
  few fat rows: the rows added one by one into each limb's sum held as its
  high and low uint32 word, ``(L, 2, B·k + B·t)``, the same integers laid
  flat with the columns on the lanes. The batched accumulator's ``(B, k)``
  and ``(B, t)`` tensors keep 5 and 2 columns in a tile's 128 lanes, which
  at 10⁸ coordinates no chip holds.

The epilogue (recombine mod p + share matmul) and the reveal take one of two
roads, by the accumulator's size (``round.FoldRound`` chooses,
``epilogue.DEVICE_ROAD_BYTES``). Below it, as for the 10⁵-wide cohorts'
accumulators of 2.2e5 to 3.2e6 B, the host's: exact and in machine integers,
each limb reduced by an int64 ``%``, the two of a wide modulus joined by
``ops.modular.mod_limbs_np``, the share matmul by ``modmatmul_np``. At or
above it, as for a few fat rows' 2.45e9 B, the device's
(``parallel/epilogue.py``): the same integers on uint32 words, the 61-bit
products by ``ops.modular.mulmod_shoup``, the clerk sums left on the chip
for the device reveal. Python integers remain only where a caller asks for
the unreduced sums (``exact_value_sums``).
"""

from __future__ import annotations

import numpy as np

from .. import telemetry
from ..ops import shamir
from ..ops.jaxcfg import ensure_x64
from ..ops.modular import (
    MAX_SAFE_MODULUS,
    WIDE_MAX_MODULUS,
    add64,
    count_wide_product,
    mod_limbs_np,
    modmatmul_np,
    modmatmul_path,
)
from .engine import AggregationPlan, _batch_secrets, _device_randomness, over_mesh

#: participant bound for exact int64 limb accumulation (see module doc)
MAX_PARTICIPANTS = 1 << 31

#: chunk bound for the int32 narrow reduction: C * (2^16 - 1) < 2^31
MAX_NARROW_CHUNK = 1 << 15

#: rows a chunk of :func:`value_limb_sums_flat` adds one by one: its rows are
#: fat (at the dims it is for, 32 int64 rows are more than a chip holds)
MAX_FLAT_CHUNK = 32


def limb_count_sum(p: int) -> int:
    """Limbs needed for exact base-2^32 sum accumulation of values < p."""
    return 1 if p <= (1 << 31) else 2


def limb_sum_road(p: int, rows: int) -> str:
    """The road a chunk's limb sums take (module doc), chosen from what
    ``value_limb_sums_chunk`` sees: the modulus' limb count and the chunk's
    row count."""
    if rows > MAX_NARROW_CHUNK:
        return "int64"
    return "int32" if limb_count_sum(p) == 1 else "halves32"


def count_limb_sum_road(road: str) -> None:
    """One limb-sum reduction of a chunk step, by its road. The road is a
    property of the traced program, not of a step: counted where it is
    chosen, once for each ``limb_sums`` call of a trace."""
    telemetry.counter(
        "sda_limb_sum_roads_total",
        "limb-sum reductions traced, by road (int32 | halves32 | int64)",
        road=road,
    ).inc()


def exact_sum_narrow(x):
    """Exact axis-0 sums using only native 32-bit lane ops: split into 2^16
    halves (logical shift on uint32), sum each in int32 (exact while
    ``x.shape[0] <= MAX_NARROW_CHUNK``), widen only the reduced result.
    ``(C, ...) -> (...)`` int64. Takes uint32 values as they are (the halves
    of a wide value), or nonneg signed values < 2^31, whose int32 cast is
    lossless and whose uint32 view is identical."""
    ensure_x64()
    import jax.numpy as jnp

    if x.shape[0] > MAX_NARROW_CHUNK:
        raise ValueError(f"narrow reduction bound is {MAX_NARROW_CHUNK} rows")
    if x.dtype != jnp.uint32:
        x = x.astype(jnp.int32).astype(jnp.uint32)
    lo = jnp.sum((x & jnp.uint32(0xFFFF)).astype(jnp.int32), axis=0, dtype=jnp.int32)
    hi = jnp.sum((x >> jnp.uint32(16)).astype(jnp.int32), axis=0, dtype=jnp.int32)
    return lo.astype(jnp.int64) + (hi.astype(jnp.int64) << jnp.int64(16))


def value_limb_sums_chunk(secrets, key, plan: AggregationPlan):
    """One streaming chunk of the sum-first hot loop.

    ``(C, dim)`` canonical secrets -> ``(L, B, K)`` int64 *exact integer*
    limb sums over the chunk's participants of the per-participant value
    rows ``[batched secrets | fresh randomness]`` (the same rows
    ``engine.share_participants`` feeds the share matmul). ``L`` is
    ``limb_count_sum(p)``. Accumulate chunks with plain ``+`` — no mod ops —
    while total participants stay below ``MAX_PARTICIPANTS``. The sums take
    the road ``limb_sum_road`` names, to the same integers by each.

    Secrets and randomness are limb-summed separately and joined on the
    tiny ``(B, ·)`` results — the big ``(C, B, K)`` concatenation the share
    matmul needs never materializes. The randomness is the program's draw
    (``engine._device_randomness``: the simulation-grade
    ``uniform_mod_device`` over the whole field), which keeps this
    bit-identical to ``share_participants`` for the same key.
    """
    ensure_x64()
    import jax
    import jax.numpy as jnp

    p = plan.modulus
    batches = _batch_secrets(secrets, plan)  # (C, b, k)
    C, nb = batches.shape[0], batches.shape[1]
    road = limb_sum_road(p, C)
    rand_shape = (C, nb, plan.rand_size)
    if road == "halves32":
        # a minor dimension of t = 2 fills two of a tile's eight sublanes
        # (3.4x the device time, PERF.md §6, PR 31). Threefry's bits depend
        # on a value's linear index alone: drawn flat, the values are the same
        rand_shape = (C, nb * plan.rand_size)
    randomness = _device_randomness(key, rand_shape, p)

    def limb_sums(x):  # (C, ...) -> (L, ...) exact integer sums
        count_limb_sum_road(road)
        if road == "int32":
            return exact_sum_narrow(x)[None]
        x = x.astype(jnp.int64)
        if limb_count_sum(p) == 1:
            return jnp.sum(x, axis=0)[None]
        words = x & jnp.int64(0xFFFFFFFF), x >> jnp.int64(32)
        if road == "halves32":
            # the compiler reads the two words as they lie and moves the
            # batching onto the reduced row; the draw stays in registers
            return jnp.stack([exact_sum_narrow(w.astype(jnp.uint32)) for w in words])
        return jnp.stack([jnp.sum(w, axis=0) for w in words])

    with jax.named_scope("fabric.input/limb_sum"):
        input_sums = limb_sums(batches)
    with jax.named_scope("fabric.rand/limb_sum"):
        rand_sums = limb_sums(randomness).reshape(-1, nb, plan.rand_size)
    return jnp.concatenate([input_sums, rand_sums], axis=-1)


def value_limb_sums_flat(secrets, key, plan: AggregationPlan):
    """:func:`value_limb_sums_chunk` for a chunk of a few fat rows, its
    accumulator laid flat and kept as uint32 words.

    ``(C, dim)`` canonical secrets -> ``(L, 2, B·k + B·t)`` uint32: for each
    limb the high and the low word of its sum down the chunk's rows, column
    by column: the secrets in the input's own order (the tail padded to
    whole batches), then the randomness in the draw's order. The same
    integers as :func:`value_limb_sums_chunk`'s ``(L, B, K)``
    (:func:`batched_limb_acc`), from the same draw (a flat ``(C, B·t)`` draw:
    threefry's bits depend on the linear index alone). Accumulate with
    :func:`add_limb_words`: the round driver's ``sum_words``.

    Why a second layout: the batched one reduces to ``(B, k)`` and ``(B,
    t)`` tensors, and the chip keeps a minor dimension of 5 or 2 in a tile
    of 128 lanes. At c5-sumfirst's 20 000 batches that is 40 MB; at 2.2e7
    batches the compiler asks for 2.2e10 B of the chip's 1.7e10 and refuses
    the step. Here every tensor keeps the columns on the lanes, and no int64
    array is handed on: the chip keeps one as two uint32 planes and splits
    it into a temporary of its own size at every program's edge, which at
    an accumulator of 2.45e9 B the chip cannot spare beside the rows.

    The rows are added one by one, every word with its carry (the road
    ``rows`` of ``sda_limb_sum_roads_total``): no partial sums of the rows
    are made, so the sum, the draw and the accumulate are one pass over the
    columns. At most :data:`MAX_FLAT_CHUNK` rows; one chip only.
    """
    ensure_x64()
    import jax
    import jax.numpy as jnp

    p = plan.modulus
    C, d = secrets.shape
    if C > MAX_FLAT_CHUNK:
        raise ValueError(f"the flat entry adds at most {MAX_FLAT_CHUNK} rows a chunk, not {C}")
    nb = -(-d // plan.input_size)
    randomness = _device_randomness(key, (C, nb * plan.rand_size), p)
    with jax.named_scope("fabric.input/batch"):
        padded = jnp.pad(secrets, ((0, 0), (0, nb * plan.input_size - d)))
    with jax.named_scope("fabric.input/limb_sum"):
        input_sums = _limb_sum_words(padded, p)
    with jax.named_scope("fabric.rand/limb_sum"):
        rand_sums = _limb_sum_words(randomness, p)
    return jnp.concatenate([input_sums, rand_sums], axis=-1)


def _limb_sum_words(x, p: int):
    """``(C, N)`` canonical -> ``(L, 2, N)`` uint32: each limb's sum down
    the rows as its high and low word, the rows added one by one."""
    import jax.numpy as jnp

    count_limb_sum_road("rows")
    u32 = jnp.uint32
    if limb_count_sum(p) == 1:
        limbs = [x.astype(u32)]
    else:
        x = x.astype(jnp.int64)
        limbs = [(x & jnp.int64(0xFFFFFFFF)).astype(u32), (x >> jnp.int64(32)).astype(u32)]
    out = []
    for limb in limbs:
        hi, lo = jnp.zeros(limb.shape[1:], u32), limb[0]
        for row in range(1, limb.shape[0]):
            hi, lo = add64(hi, lo, u32(0), limb[row])
        out.append(jnp.stack([hi, lo]))
    return jnp.stack(out)


def add_limb_words(acc, out):
    """The ``sum_words`` accumulate rule: two ``(L, 2, N)`` word
    accumulators of :func:`value_limb_sums_flat` added, every limb in 64
    bits (exact while the participants stay below ``MAX_PARTICIPANTS``)."""
    import jax.numpy as jnp

    hi, lo = add64(acc[:, 0], acc[:, 1], out[:, 0], out[:, 1])
    return jnp.stack([hi, lo], axis=1)


def batched_limb_acc(word_acc, plan: AggregationPlan):
    """The word accumulator of :func:`value_limb_sums_flat` as the batched
    ``(L, B, K)`` int64 one of :func:`value_limb_sums_chunk`: the same
    integers, on the host."""
    words = np.asarray(word_acc).astype(np.int64)
    acc = (words[:, 0] << 32) | words[:, 1]
    B, k = plan.n_batches, plan.input_size
    secrets = acc[:, : B * k].reshape(acc.shape[0], B, k)
    randomness = acc[:, B * k :].reshape(acc.shape[0], B, plan.rand_size)
    return np.concatenate([secrets, randomness], axis=-1)


def exact_value_sums(limb_acc):
    """``(L, B, K)`` int64 limb accumulator -> ``(B, K)`` exact integer
    participant sums (object dtype, python ints — no modulus applied)."""
    acc = np.asarray(limb_acc, dtype=object)
    out = np.zeros(acc.shape[1:], dtype=object)
    for w in range(acc.shape[0]):
        out = out + acc[w] * (1 << (32 * w))
    return out


def _value_sums_mod(limb_acc, p: int):
    """``(L, B, K)`` limb accumulator -> ``((B, K)`` canonical int64 value
    sums mod p, the road taken``)``. Each limb is reduced first by an int64
    ``%`` (exact; so whatever the participant count, up to limb sums of
    2⁶³ − 1, the joined value stays under 2³³·p): one limb is that alone
    (``int64``), two go through ``mod_limbs_np`` (``limb``). What that road
    refuses (an accumulator that is not machine integers, more than two
    limbs, p ≥ 2⁶²) takes python integers (``object``)."""
    acc = np.asarray(limb_acc)
    if acc.dtype.kind == "i":
        acc = acc.astype(np.int64, copy=False)
        if acc.shape[0] == 1:
            return acc[0] % p, "int64"
        if acc.shape[0] == 2 and p < WIDE_MAX_MODULUS:
            return mod_limbs_np([acc[0] % p, acc[1] % p], 32, p), "limb"
    return (exact_value_sums(limb_acc) % p).astype(np.int64), "object"


def clerk_sums_from_limb_acc(limb_acc, plan: AggregationPlan):
    """Host epilogue: ``(L, B, K)`` int64 limb accumulator -> clerk sums.

    Returns ``(clerk_sums, value_sums)``: ``clerk_sums`` is the ``(n, B)``
    int64 canonical per-clerk share sums (exactly what per-participant
    sharing + clerk-combine produces), ``value_sums`` the ``(B, K)``
    canonical participant-sums (whose first ``k`` columns are the plain
    batched secret sums — the free verification handle). All arithmetic on
    this tiny accumulator is exact and vectorised (``_value_sums_mod``,
    ``modmatmul_np``); each span's ``path`` says the road it took.
    """
    p = plan.modulus
    if plan.share_matrix is None:
        raise ValueError("sum-first epilogue requires a packed share matrix")
    bits = p.bit_length()
    with telemetry.span(
        "fabric.epilogue.recombine", modulus_bits=bits, shape=np.shape(limb_acc)
    ) as record:
        vsum, path = _value_sums_mod(limb_acc, p)
        if path != "int64" and p >= MAX_SAFE_MODULUS:
            count_wide_product(path)
        if record is not None:
            record["attrs"]["path"] = path
    with telemetry.span(
        "fabric.epilogue.share_matmul", modulus_bits=bits, shape=vsum.shape
    ) as record:
        S_T = plan.share_matrix.T.astype(np.int64)  # (K, n)
        clerk = modmatmul_np(vsum, S_T, p)  # (B, n) in (-p, p)
        clerk = np.where(clerk < 0, clerk + p, clerk).astype(np.int64)
        if record is not None:
            record["attrs"]["path"] = modmatmul_path(vsum, S_T, p)
    return clerk.T.copy(), vsum


def clerk_sums_sum_first(secrets, key, plan: AggregationPlan):
    """Single-shot convenience: ``(P, dim)`` -> ``(n, B)`` clerk sums.

    Parity twin of ``share_participants`` + ``clerk_combine`` + rem (see
    tests/test_sumfirst.py); the benchmark's rounds
    (``benchmark/rounds/packed_fold.py``) drive the chunk / epilogue pieces
    directly.
    """
    if secrets.shape[0] > MAX_PARTICIPANTS:
        raise ValueError(f"chunk the input: exact bound is {MAX_PARTICIPANTS}")
    acc = value_limb_sums_chunk(secrets, key, plan)
    clerk, _ = clerk_sums_from_limb_acc(np.asarray(acc), plan)
    return clerk


def reconstruct_from_clerk_sums(clerk_sums, indices, scheme, dim: int):
    """Host-exact reconstruction for any modulus width (tiny inputs; the
    rounds' epilogue). Same helper backs ``engine.reconstruct``'s wide path."""
    return shamir.reconstruct_clerk_sums_host(clerk_sums, indices, scheme, dim)


def sharded_value_limb_sums(plan: AggregationPlan, mesh):
    """The sum-first hot loop over a device mesh: each device limb-sums its
    own participant shard (``value_limb_sums_chunk``), then one int64
    ``psum`` over the participant axis ``p`` carries only the tiny
    ``(L, B, K)`` accumulator across ICI — the sharded twin of the
    single-chip chunk loop, with the same exactness bound
    (``MAX_PARTICIPANTS`` *total*, summed over shards, since the psum adds
    pre-bounded per-shard limb sums).

    Returns ``fn(secrets_sharded, key) -> (L, B, K)`` int64 limb sums
    (replicated over ``p``, sharded over ``d`` on the B axis): the chunk
    step of ``engine.over_mesh``, which the round driver's step over a mesh
    is too. Feed the gathered result to :func:`clerk_sums_from_limb_acc` on
    host, exactly like the single-chip chunks.
    """
    p_size = mesh.shape["p"]

    def bounded(secrets, key):
        # a chip's shapes are static, so this enforces the documented
        # *global* exactness bound at trace time (psum adds p_size shards),
        # mirroring clerk_sums_sum_first's guard
        if secrets.shape[0] * p_size > MAX_PARTICIPANTS:
            raise ValueError(
                f"global participant count {secrets.shape[0] * p_size} "
                f"exceeds the exact limb-sum bound {MAX_PARTICIPANTS}; "
                "chunk the input"
            )
        return value_limb_sums_chunk(secrets, key, plan)

    return over_mesh(bounded, plan, mesh)
