"""The round driver over a mesh (``sda_tpu.parallel.round``, ``fold_round(...,
mesh=)``) on the CPU's virtual devices: the sharded masked round reveals what
the one-chip masked round reveals, every chip draws seeds of its own (and a
step body that leaves the mesh position out of its key is caught), the
driver's unmasked step is the sharded fabrics' program text for text, the
recipient's sharded combine is ``combine_masks_device`` bit for bit, its
partials meet without leaving int64's range at eight chips, both kernels run
inside the ``shard_map`` body (on the interpreter), and what the mesh round
does not do is refused."""

import functools

import numpy as np
import pytest

from sda_tpu import telemetry
from sda_tpu.ops import chacha_pallas, find_packed_parameters
from sda_tpu.ops.jaxcfg import ensure_x64
from sda_tpu.protocol import ChaChaMasking, PackedShamirSharing

ensure_x64()

import jax
import jax.numpy as jnp

from sda_tpu.crypto.masking import ChaChaMasker
from sda_tpu.parallel import engine, fold_round, make_mesh, shard_participants, sumfirst

DIM, CHUNK, STEPS = 20, 8, 2
K, T, N = 5, 2, 8
ENTRY = sumfirst.value_limb_sums_chunk


@functools.lru_cache(maxsize=None)
def scheme():
    p, w2, w3 = find_packed_parameters(K, T, N, min_modulus_bits=60, seed=0)
    return PackedShamirSharing(K, N, T, p, w2, w3)


def masking():
    return ChaChaMasking(scheme().prime_modulus, DIM, 128)


def rows_of(seed=0, count=STEPS * CHUNK):
    p = scheme().prime_modulus
    return np.random.default_rng(seed).integers(0, p, size=(count, DIM)).astype(np.int64)


def column_sums(rows):
    p = scheme().prime_modulus
    return np.array([sum(int(v) for v in rows[:, j]) % p for j in range(rows.shape[1])])


def chunks_over(rows, mesh):
    return [
        shard_participants(jnp.asarray(rows[i : i + CHUNK]), mesh)
        for i in range(0, len(rows), CHUNK)
    ]


def counters(*names):
    values = {}
    for c in telemetry.snapshot(0)["counters"]:
        values[c["name"]] = values.get(c["name"], 0) + c["value"]
    return [values.get(name, 0) for name in names]


@functools.lru_cache(maxsize=None)
def one_chip_aggregate():
    """The one-chip masked round over ``rows_of()``: ``(masked aggregate,
    aggregate)``, its few seeds combined on the host."""
    driver = fold_round(scheme(), DIM, ENTRY, CHUNK, masking=masking())
    rows = rows_of()
    chunks = [jnp.asarray(rows[i : i + CHUNK]) for i in range(0, len(rows), CHUNK)]
    acc, seeds, counts = driver.fold_chunks(chunks, jax.random.key(3))
    assert driver.short_windows(counts) == 0
    masked = driver.reveal(driver.clerk_sums(acc), range(7))
    uploads = list(np.concatenate([np.asarray(s) for s in seeds]).astype(np.int64))
    return masked, driver.unmask(masked, uploads)


@pytest.mark.parametrize("p_size", [2, 4])
def test_the_sharded_masked_round_reveals_what_the_one_chip_round_reveals(p_size, monkeypatch):
    """Rows over ``p``, the seeds and counts handed on sharded over ``p``, the
    recipient's fold where the seeds lie: the plain sum, which is the one-chip
    ``fold_round(..., masking=)`` round's aggregate over the same rows; and no
    two rows of the round share a seed, on one chip or across chips."""
    monkeypatch.setattr(ChaChaMasker, "DEVICE_COMBINE_THRESHOLD", 1)
    mesh = make_mesh(p_size=p_size, d_size=1)
    driver = fold_round(scheme(), DIM, ENTRY, CHUNK, masking=masking(), mesh=mesh)
    assert driver.mesh is mesh and driver.chunk == CHUNK
    rows = rows_of()
    acc, seeds, counts = driver.fold_chunks(chunks_over(rows, mesh), jax.random.key(3))
    assert acc.shape == driver.acc_shape and acc.sharding.is_fully_replicated
    assert len(seeds) == len(counts) == STEPS
    for step_seeds, step_counts in zip(seeds, counts):
        assert step_seeds.shape == (CHUNK, 4) and step_seeds.dtype == jnp.uint32
        assert step_seeds.sharding.shard_shape(step_seeds.shape) == (CHUNK // p_size, 4)
        assert step_counts.sharding.shard_shape(step_counts.shape) == (CHUNK // p_size,)
    assert driver.short_windows([np.asarray(c) for c in counts]) == 0
    seed_rows = np.concatenate([np.asarray(s) for s in seeds])
    assert len({tuple(row) for row in seed_rows}) == STEPS * CHUNK
    masked = driver.reveal(driver.clerk_sums(acc), range(7))
    want = column_sums(rows)
    assert not np.array_equal(masked, want), "the clerks' sums carry no mask"
    one_chip_masked, one_chip = one_chip_aggregate()
    assert np.array_equal(one_chip, want)
    assert not np.array_equal(masked, one_chip_masked), "other seeds, other masks"
    # the device arrays the steps returned, folded where they lie
    telemetry.reset()
    assert np.array_equal(driver.unmask(masked, seeds, chunk=CHUNK // p_size), one_chip)
    assert counters(
        "sda_crypto_chacha_expands_total", "sda_crypto_chacha_folds_total",
        "sda_crypto_chacha_fold_chips_total",
    ) == [STEPS * CHUNK, STEPS, STEPS * p_size]
    (combine,) = telemetry.spans("fabric.unmask.combine")
    assert combine["attrs"] == {"seeds": STEPS * CHUNK, "path": "device", "chips": p_size}
    # as a recipient receives them: host rows, put sharded (the same program)
    uploads = list(seed_rows.astype(np.int64))
    assert np.array_equal(driver.unmask(masked, uploads, chunk=CHUNK // p_size), one_chip)


def cheap_expansion(monkeypatch):
    """The mask stage without its ChaCha programs: a test of the keys and the
    seeds need not compile them."""

    def expand_seeds_counts(seeds, dim, modulus, backend="jnp"):
        masks = jnp.broadcast_to(seeds[:, :1].astype(jnp.int64), (seeds.shape[0], dim))
        return masks, jnp.full((seeds.shape[0],), dim, jnp.int32)

    monkeypatch.setattr(chacha_pallas, "expand_seeds_counts", expand_seeds_counts)


def round_seeds(driver, mesh):
    _acc, seeds, _counts = driver.fold_chunks(chunks_over(rows_of(), mesh), jax.random.key(5))
    return np.concatenate([np.asarray(s) for s in seeds])


def test_a_step_body_whose_key_skips_the_mesh_position_repeats_its_seeds(monkeypatch):
    """Every chip's seeds are its own because the driver's ``shard_map`` body
    folds the chip's mesh position into the key; a body that does not hands
    every chip the same seeds, which no aggregate shows (equal masks cancel as
    well as distinct ones): the seeds themselves do."""
    cheap_expansion(monkeypatch)
    mesh = make_mesh(p_size=4, d_size=1)
    healthy = round_seeds(fold_round(scheme(), DIM, ENTRY, CHUNK, masking=masking(), mesh=mesh), mesh)
    assert len({tuple(row) for row in healthy}) == STEPS * CHUNK
    monkeypatch.setattr(engine, "fold_mesh_axes", lambda key, mesh: key)
    faulty = round_seeds(fold_round(scheme(), DIM, ENTRY, CHUNK, masking=masking(), mesh=mesh), mesh)
    own = CHUNK // 4  # a chip's rows of a step: all that are distinct in it
    assert len({tuple(row) for row in faulty}) == STEPS * own
    for step in faulty.reshape(STEPS, 4, own, -1):
        assert all(np.array_equal(step[0], chip) for chip in step[1:])


@pytest.mark.parametrize("p_size,d_size", [(2, 2), (4, 1)])
def test_the_unmasked_round_over_a_mesh_reveals_the_plain_sum(p_size, d_size):
    """The unmasked step keeps ``d``: dim 20 is two batches of five a d-shard."""
    mesh = make_mesh(p_size=p_size, d_size=d_size)
    driver = fold_round(scheme(), DIM, ENTRY, CHUNK, mesh=mesh)
    rows = rows_of(seed=2)
    acc = driver.fold_chunks(chunks_over(rows, mesh), jax.random.key(4))
    assert acc.shape == driver.acc_shape == np.asarray(driver.zero_acc()).shape
    got = driver.reveal(driver.clerk_sums(acc), range(1, 8))
    assert np.array_equal(got, column_sums(rows))


def step_around(sharded, accumulate, modulus):
    """The chunk step a caller writes around a sharded fabric, as the
    benchmark's sum-first cell over a mesh does."""

    def step(acc, chunk, key, i):
        acc = acc + sharded(chunk, jax.random.fold_in(key, i))
        if accumulate == "sum_mod_p":
            acc = jax.lax.rem(acc, jnp.int64(modulus))
        return acc

    return jax.jit(step)


@pytest.mark.parametrize("p_size,d_size", [(2, 2), (4, 1)])
@pytest.mark.parametrize("fabric", ["sum-first", "limb"])
def test_the_drivers_unmasked_mesh_step_is_the_sharded_fabrics_program(fabric, p_size, d_size):
    """One row-sharded wrapper builds the three: the driver's unmasked step
    over a mesh lowers to the text of the same step around
    ``sumfirst.sharded_value_limb_sums``, and for the limb entry around
    ``TpuAggregator.sharded_limb_accumulators``."""
    mesh = make_mesh(p_size=p_size, d_size=d_size)
    modulus = scheme().prime_modulus
    if fabric == "sum-first":
        plan = engine.make_plan(scheme(), DIM)
        entry, sharded = ENTRY, sumfirst.sharded_value_limb_sums(plan, mesh)
    else:
        aggregator = engine.TpuAggregator(scheme(), DIM, mesh=mesh)
        entry, sharded = engine.share_combine_limb, aggregator.sharded_limb_accumulators()
    driver = fold_round(scheme(), DIM, entry, CHUNK, mesh=mesh)
    args = (driver.zero_acc(), chunks_over(rows_of(), mesh)[0], jax.random.key(0), np.int32(0))
    want = step_around(sharded, driver.accumulate, modulus).lower(*args).as_text()
    assert driver.step.lower(*args).as_text() == want


def seventeen_seeds():
    seeds = np.random.default_rng(17).integers(0, 1 << 32, size=(17, 4), dtype=np.uint64)
    return seeds.astype(np.uint32)


@functools.lru_cache(maxsize=None)
def one_chip_combine():
    seeds, p = seventeen_seeds(), scheme().prime_modulus
    return np.asarray(chacha_pallas.combine_masks_device(seeds, DIM, p, chunk=len(seeds)))


@pytest.mark.parametrize("chips", [2, 4, 8])
def test_the_sharded_combine_is_the_one_chip_combine_bit_for_bit(chips):
    """The same seeds, 61-bit modulus: host rows put sharded, two folds of
    eight seeds on all the chips, and the one seed that does not divide over
    them folded on the host."""
    seeds, p = seventeen_seeds(), scheme().prime_modulus
    want = one_chip_combine()
    mesh = make_mesh(p_size=chips, d_size=1)
    telemetry.reset()
    got = chacha_pallas.combine_masks_device(seeds, DIM, p, chunk=CHUNK // chips, mesh=mesh)
    assert got.sharding.is_fully_replicated and np.array_equal(np.asarray(got), want)
    assert counters(
        "sda_crypto_chacha_expands_total", "sda_crypto_chacha_folds_total",
        "sda_crypto_chacha_fold_chips_total",
    ) == [len(seeds), 2, 2 * chips]


def test_the_partials_meet_without_leaving_int64_at_eight_chips(monkeypatch):
    """Eight canonical 61-bit partials of p - 1 each: a plain int64 ``psum``
    of them is past 2^63; gathered and summed by halving they are 8 (p - 1)
    mod p = p - 8. A short window on two chips: those chips' batches alone are
    recovered on the host, and counted."""
    p = scheme().prime_modulus
    assert 8 * (p - 1) >= 1 << 63
    mesh = make_mesh(p_size=8, d_size=1)
    short_on = {2: 1, 5: 2}  # chip -> rows of its batch whose window came short

    def fold_chunk(batch, dim, modulus, backend):
        part = jnp.full((dim,), modulus - 1, jnp.int64)
        chip = jax.lax.axis_index("p")
        short = sum(jnp.where(chip == c, n, 0) for c, n in short_on.items())
        counts = jnp.where(jnp.arange(batch.shape[0]) < short, dim - 1, dim).astype(jnp.int32)
        return part, counts

    monkeypatch.setattr(chacha_pallas, "_fold_chunk", fold_chunk)
    chacha_pallas.fold_chunk_mesh_jit.cache_clear()
    try:
        seeds = np.arange(8 * 3 * 4, dtype=np.uint32).reshape(8 * 3, 4)
        met, parts, counts = chacha_pallas.fold_chunk_mesh_jit(mesh)(seeds, DIM, p, "jnp")
        assert np.array_equal(np.asarray(met), np.full(DIM, p - 8))
        assert np.asarray(parts).shape == (8, DIM) and counts.shape == (8 * 3,)
        telemetry.reset()
        recovered = []

        def host_fold(batch, dim, modulus):
            recovered.append(np.array(batch))
            return np.full((dim,), 1, np.int64)

        monkeypatch.setattr(chacha_pallas, "_host_fold", host_fold)
        got = chacha_pallas.combine_masks_device(seeds, DIM, p, chunk=3, mesh=mesh)
        # six chips' partials of p - 1 and the two recovered ones of 1
        assert np.array_equal(np.asarray(got), np.full(DIM, (6 * (p - 1) + 2) % p))
        assert [batch.tolist() for batch in recovered] == [
            seeds[6:9].tolist(), seeds[15:18].tolist()
        ]
        short = [
            c["value"] for c in telemetry.snapshot(0)["counters"]
            if c["name"] == "sda_mask_slack_exhausted_total" and c["labels"] == {"side": "recipient"}
        ]
        assert short == [3]
    finally:
        chacha_pallas.fold_chunk_mesh_jit.cache_clear()


def test_seeds_that_lie_on_the_chips_are_folded_in_slices_of_every_chips_own_rows(monkeypatch):
    """Device arrays longer than ``chunk`` rows a chip: every call takes the
    next ``chunk`` of every chip's own rows (the last call what is left), no
    row twice and none left out; with a fold that sums the seeds' first words
    the combined "mask" is their sum."""
    p = scheme().prime_modulus
    mesh = make_mesh(p_size=4, d_size=1)

    def fold_chunk(batch, dim, modulus, backend):
        part = jnp.full((dim,), jnp.sum(batch[:, 0].astype(jnp.int64)), jnp.int64)
        return part, jnp.full((batch.shape[0],), dim, jnp.int32)

    monkeypatch.setattr(chacha_pallas, "_fold_chunk", fold_chunk)
    chacha_pallas.fold_chunk_mesh_jit.cache_clear()
    try:
        rng = np.random.default_rng(3)
        arrays = [rng.integers(1, 1 << 20, size=(rows, 4)).astype(np.uint32) for rows in (20, 8)]
        lying = [shard_participants(jnp.asarray(a), mesh) for a in arrays]
        telemetry.reset()
        got = chacha_pallas.combine_masks_device(lying, DIM, p, chunk=2, mesh=mesh)
        want = sum(int(a[:, 0].astype(np.int64).sum()) for a in arrays) % p
        assert np.array_equal(np.asarray(got), np.full(DIM, want))
        # five rows a chip in calls of 2, 2 and 1; two rows a chip as they lie
        assert counters(
            "sda_crypto_chacha_expands_total", "sda_crypto_chacha_folds_total",
            "sda_crypto_chacha_fold_chips_total",
        ) == [28, 4, 16]
    finally:
        chacha_pallas.fold_chunk_mesh_jit.cache_clear()


def test_both_kernels_run_inside_the_shard_map_body_to_the_hosts_bits():
    """The sharded fold with the rounds and the compaction on the Pallas
    interpreter, every chip over its own seeds, against the host's expansion
    (numpy, which shares no code with the kernels); and a chip's own partial
    is its own seeds' sum."""
    p = scheme().prime_modulus
    dim = 150  # a window of at least a lane tile: the compaction kernel's
    mesh = make_mesh(p_size=2, d_size=1)
    seeds = seventeen_seeds()[:4]
    before = counters("sda_crypto_chacha_compactions_total", "sda_crypto_chacha_blocks_total")
    met, parts, counts = chacha_pallas.fold_chunk_mesh_jit(mesh)(seeds, dim, p, "interpret")
    after = counters("sda_crypto_chacha_compactions_total", "sda_crypto_chacha_blocks_total")
    assert all(now > then for now, then in zip(after, before))
    assert np.array_equal(np.asarray(met), chacha_pallas._host_fold(seeds, dim, p))
    assert np.array_equal(np.asarray(parts)[1], chacha_pallas._host_fold(seeds[2:], dim, p))
    assert counts.shape == (4,) and int(np.asarray(counts).min()) >= dim


def test_what_the_round_over_a_mesh_does_not_do_is_refused():
    mesh = make_mesh(p_size=2, d_size=2)
    with pytest.raises(ValueError, match="d = 1"):
        fold_round(scheme(), DIM, ENTRY, CHUNK, masking=masking(), mesh=mesh)
    with pytest.raises(ValueError, match="p = 2"):
        fold_round(scheme(), DIM, ENTRY, CHUNK + 1, mesh=mesh)
    with pytest.raises(ValueError, match="whole batches"):
        fold_round(scheme(), DIM + 1, ENTRY, CHUNK, mesh=mesh)
    driver = fold_round(scheme(), DIM, ENTRY, CHUNK, mesh=mesh)
    with pytest.raises(ValueError, match="one chip's"):
        driver.fold_host_rows([rows_of()], jax.random.key(0), in_flight=2)
    # seeds on the device that do not divide over the chips are not silently cut
    flat = make_mesh(p_size=4, d_size=1)
    with pytest.raises(ValueError, match="do not divide"):
        chacha_pallas.combine_masks_device(
            [jnp.zeros((6, 4), jnp.uint32)], DIM, scheme().prime_modulus, chunk=2, mesh=flat
        )
