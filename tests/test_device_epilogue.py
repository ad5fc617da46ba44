"""The device road of the sum-first epilogue and reveal
(``sda_tpu/parallel/epilogue.py``), the flat word accumulator it takes
(``sumfirst.value_limb_sums_flat``) and the 61-bit multiply under both
(``ops.modular.mulmod_shoup``): bit for bit the host road's, and the exact
column sum's, at a 61-bit and a 31-bit modulus; and the round driver's choice
of road by the accumulator's size, counted."""

import itertools

import numpy as np
import pytest

from sda_tpu.ops import find_packed_parameters
from sda_tpu.protocol import PackedShamirSharing

PRIME_61 = find_packed_parameters(5, 2, 8, min_modulus_bits=60, seed=0)[0]
PRIME_31 = find_packed_parameters(5, 2, 8, min_modulus_bits=30, seed=0)[0]
BITS = {"61-bit": 60, "31-bit": 30}


def scheme_of(bits):
    p, w2, w3 = find_packed_parameters(5, 2, 8, min_modulus_bits=bits, seed=0)
    return PackedShamirSharing(5, 8, 2, p, w2, w3)


@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks of 128 batches: the programs' loop runs several blocks, the
    last overlapping the one before where 128 does not divide the batches."""
    from sda_tpu.parallel import epilogue

    monkeypatch.setattr(epilogue, "BLOCK_BATCHES", 128)


def rows_of(scheme, dim, rows, seed):
    from sda_tpu.parallel.round import _input_dtype

    p = scheme.prime_modulus
    top = 1 << (p.bit_length() - 1)
    return np.random.default_rng(seed).integers(0, top, (rows, dim)).astype(_input_dtype(p))


# ---------------------------------------------------------------------------
# the 61-bit multiply
# ---------------------------------------------------------------------------


def words_of(values):
    values = np.asarray(values, dtype=np.uint64)
    return (values >> np.uint64(32)).astype(np.uint32), values.astype(np.uint32)


def joined(hi, lo):
    return [(int(h) << 32) | int(l) for h, l in zip(np.asarray(hi), np.asarray(lo))]


@pytest.mark.parametrize("p", [PRIME_61, PRIME_31, (1 << 61) - 1, (1 << 62) - 57, 3])
def test_mulmod_shoup_is_python_integers_on_edge_operands(p):
    import jax

    from sda_tpu.ops.modular import mulmod_shoup, shoup_words

    rng = np.random.default_rng(p % 1000)
    a = [0, 1, p - 1, p, p + 1, 2 * p - 1, (1 << 32) - 1, 1 << 32, (1 << 63) - 1, (1 << 64) - 1]
    a += [int(x) for x in rng.integers(0, 1 << 63, 40, dtype=np.uint64)]
    a += [int(x) | (1 << 63) for x in rng.integers(0, 1 << 63, 40, dtype=np.uint64)]
    hi, lo = words_of(a)
    multipliers = [0, 1, 2, p - 1, p - 2, p // 2, (1 << 32) % p] + [
        int(x) % p for x in rng.integers(0, 1 << 62, 8)
    ]
    for c in multipliers:
        product = jax.jit(lambda h, l, c=c: mulmod_shoup(h, l, shoup_words(c, p), p))
        assert joined(*product(hi, lo)) == [x * c % p for x in a], c


def test_mulmod_shoup_takes_its_multiplier_as_an_operand():
    """The reveal's Lagrange matrix comes in as words: the same products as
    a multiplier folded into the program."""
    import jax
    import jax.numpy as jnp

    from sda_tpu.ops.modular import mulmod_shoup, shoup_words

    p = PRIME_61
    a = [3, p - 1, (1 << 64) - 1, 12345678901234567]
    hi, lo = words_of(a)
    c = p - 3
    operand = jnp.asarray(shoup_words(c, p), dtype=jnp.uint32)
    got = jax.jit(lambda h, l, w: mulmod_shoup(h, l, tuple(w), p))(hi, lo, operand)
    assert joined(*got) == [x * c % p for x in a]


@pytest.mark.parametrize("c,p", [(5, 5), (-1, 7), (0, 1 << 62), (0, 1)])
def test_shoup_words_refuse_a_multiplier_or_modulus_out_of_range(c, p):
    from sda_tpu.ops.modular import shoup_words

    with pytest.raises(ValueError):
        shoup_words(c, p)


# ---------------------------------------------------------------------------
# the flat word accumulator
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("width", sorted(BITS))
@pytest.mark.parametrize("dim", [640, 1923])
def test_the_flat_entry_sums_the_batched_entrys_integers(width, dim):
    import jax

    from sda_tpu.parallel import fold_round, sumfirst

    scheme = scheme_of(BITS[width])
    batched = fold_round(scheme, dim, sumfirst.value_limb_sums_chunk, 3)
    flat = fold_round(scheme, dim, sumfirst.value_limb_sums_flat, 3)
    x = rows_of(scheme, dim, 6, dim)
    key = jax.random.key(11)
    want = np.asarray(batched.fold_chunks([x[:3], x[3:]], key))
    got = flat.fold_chunks([x[:3], x[3:]], key)
    assert got.dtype == np.uint32 and got.shape == flat.acc_shape
    assert np.array_equal(sumfirst.batched_limb_acc(np.asarray(got), flat.plan), want)


def test_the_flat_entry_refuses_many_rows_and_a_mesh():
    import jax

    from sda_tpu.parallel import fold_round, make_mesh, sumfirst
    from sda_tpu.parallel.engine import make_plan

    scheme = scheme_of(60)
    plan = make_plan(scheme, 10)
    rows = np.zeros((sumfirst.MAX_FLAT_CHUNK + 1, 10), np.int64)
    with pytest.raises(ValueError, match="rows a chunk"):
        sumfirst.value_limb_sums_flat(rows, jax.random.key(0), plan)
    mesh = make_mesh(1, 1)
    with pytest.raises(ValueError, match="one chip's"):
        fold_round(scheme, 10, sumfirst.value_limb_sums_flat, 2, mesh=mesh)


# ---------------------------------------------------------------------------
# the device road against the host road and the exact sum
# ---------------------------------------------------------------------------


#: ``(batches a block, dim)``: at k = 5, 1923 is 385 batches, not whole rows
#: of 128 (four blocks of a row, or one of four rows); 1280 is 256, two whole
#: rows; 7041 is 1409 batches, 12 rows, so that the second block of 8 rows
#: overlaps the first
BLOCKS = [(128, 1923), (1024, 1923), (128, 1280), (1024, 7041)]


@pytest.mark.parametrize("block,dim", BLOCKS)
@pytest.mark.parametrize("width", sorted(BITS))
def test_device_clerk_sums_and_every_reveal_of_7_of_8_are_the_host_roads_and_the_exact_sum(
    width, block, dim, monkeypatch
):
    import jax

    from sda_tpu.ops import shamir
    from sda_tpu.parallel import epilogue, fold_round, sumfirst

    monkeypatch.setattr(epilogue, "BLOCK_BATCHES", block)
    scheme = scheme_of(BITS[width])
    p = scheme.prime_modulus
    driver = fold_round(scheme, dim, sumfirst.value_limb_sums_flat, 4)
    x = rows_of(scheme, dim, 8, 7)
    acc = driver.fold_chunks([x[:4], x[4:]], jax.random.key(2))
    device = driver.epilogue_program(acc)
    host, _ = sumfirst.clerk_sums_from_limb_acc(
        sumfirst.batched_limb_acc(np.asarray(acc), driver.plan), driver.plan
    )
    assert np.array_equal(np.asarray(device), host)
    exact = np.array([int(v) % p for v in x.astype(object).sum(axis=0)], dtype=np.int64)
    for clerks in itertools.combinations(range(8), 7):
        # the reveal's words: one (2·dim,) array, low words at even places
        words = np.asarray(
            driver.reveal_program(
                device, np.asarray(clerks, np.int32), epilogue.lagrange_operand(scheme, clerks)
            )
        )
        assert words.dtype == np.uint32 and words.shape == (2 * dim,), clerks
        assert np.array_equal(words[0::2], exact.astype(np.uint32)), clerks
        assert np.array_equal(words[1::2], (exact >> 32).astype(np.uint32)), clerks
        got = driver.reveal(device, list(clerks))
        assert got.dtype == np.int64 and np.array_equal(got, exact), clerks
        by_host = shamir.reconstruct_clerk_sums_host(host, list(clerks), scheme, dim)
        assert np.array_equal(np.mod(np.asarray(by_host).astype(np.int64), p), got)
    # the reveal's program is one for every subset: the Lagrange matrix is an operand
    assert driver.reveal_program._cache_size() == 1
    assert epilogue.BLOCK_BATCHES == block


@pytest.mark.parametrize("byteorder", ["little", "big"])
def test_fetch_aggregate_views_its_words_on_a_little_endian_host_and_joins_them_elsewhere(
    byteorder, monkeypatch
):
    """The words cross under their span and counter and are deleted on the
    device; on a little-endian host the int64 aggregate shares their memory
    (no pass, no new array), on a big-endian one they are joined; either way
    the caller may change it, as the host road's."""
    import sys

    import jax
    import jax.numpy as jnp

    from sda_tpu import telemetry
    from sda_tpu.parallel import epilogue

    def fetched_bytes():
        snap = telemetry.snapshot(0)
        return sum(
            c["value"]
            for c in snap["counters"]
            if c["name"] == "sda_fabric_reveal_fetch_bytes_total"
        )

    host = []
    device_get = jax.device_get

    def kept(x):
        host.append(device_get(x))
        return host[-1]

    monkeypatch.setattr(jax, "device_get", kept)
    monkeypatch.setattr(sys, "byteorder", byteorder)
    values = np.array([0, 1, (1 << 32) - 1, 1 << 32, PRIME_61 - 1, 123456789012345], np.int64)
    low_high = np.stack([values.astype(np.uint32), (values >> 32).astype(np.uint32)], axis=1)
    words = jnp.asarray(low_high.reshape(-1))
    before = fetched_bytes()
    got = epilogue.fetch_aggregate(words)
    assert got.dtype == np.int64 and got.shape == values.shape and got.flags.c_contiguous
    assert np.array_equal(got, values)
    assert np.shares_memory(got, host[0]) == (byteorder == "little")
    assert fetched_bytes() == before + 8 * values.size
    assert words.is_deleted() and got.flags.writeable
    got[1] = 7
    assert got[1] == 7 and np.array_equal(got[2:], values[2:])


@pytest.mark.parametrize("width", sorted(BITS))
def test_limb_sums_at_their_bound_recombine_as_on_the_host(width, small_blocks):
    """Every limb sum near 2⁶³ − 1, as 2³¹ participants' would be: the
    recombine's products and sums stay exact."""
    import jax.numpy as jnp

    from sda_tpu.parallel import epilogue, sumfirst
    from sda_tpu.parallel.engine import make_plan

    scheme = scheme_of(BITS[width])
    plan = make_plan(scheme, 5 * 300)
    limbs = sumfirst.limb_count_sum(scheme.prime_modulus)
    columns = plan.n_batches * 7
    rng = np.random.default_rng(3)
    sums = (1 << 63) - 1 - rng.integers(0, 1 << 40, (limbs, columns), dtype=np.int64)
    sums[:, 0] = (1 << 63) - 1
    words = np.stack([(sums >> 32).astype(np.uint32), sums.astype(np.uint32)], axis=1)
    device = epilogue.clerk_sums_program(plan)(jnp.asarray(words))
    host, _ = sumfirst.clerk_sums_from_limb_acc(sumfirst.batched_limb_acc(words, plan), plan)
    assert np.array_equal(np.asarray(device), host)


def test_a_batched_accumulator_takes_the_device_road_laid_flat(small_blocks):
    import jax

    from sda_tpu.parallel import epilogue, fold_round, sumfirst

    scheme = scheme_of(60)
    driver = fold_round(scheme, 1923, sumfirst.value_limb_sums_chunk, 4)
    acc = driver.fold_chunks([rows_of(scheme, 1923, 4, 1)], jax.random.key(4))
    words = epilogue.word_limb_acc(acc, driver.plan)
    assert words.shape == (2, 2, driver.plan.n_batches * 7)
    host = driver.epilogue(np.asarray(acc), driver.plan)
    assert np.array_equal(np.asarray(driver.epilogue_program(words)), host)


# ---------------------------------------------------------------------------
# the road, chosen by the accumulator's size and counted
# ---------------------------------------------------------------------------


def roads():
    from sda_tpu import telemetry

    snap = telemetry.snapshot(0)
    return {
        c["labels"]["road"]: c["value"]
        for c in snap["counters"]
        if c["name"] == "sda_fabric_epilogue_roads_total"
    }


#: the accumulators of the eight cells of the 10⁵-wide cohorts, by (dim,
#: modulus bits, entry): c5-sumfirst, -x4, c5-masked, the two fed cells and
#: c5-masked-x4 share the first; c4-participant and c4-sumfirst the others
STANDING = [
    (100_000, 60, "sda_tpu.parallel.sumfirst.value_limb_sums_chunk", 500),
    (50_000, 30, "sda_tpu.parallel.engine.share_combine_limb", 2000),
    (50_000, 30, "sda_tpu.parallel.sumfirst.value_limb_sums_chunk", 2000),
]


def entry_of(dotted):
    import importlib

    module, _, name = dotted.rpartition(".")
    return getattr(importlib.import_module(module), name)


@pytest.mark.parametrize("dim,bits,entry,chunk", STANDING)
def test_the_standing_cells_accumulators_take_the_host_road(dim, bits, entry, chunk):
    from sda_tpu.parallel import epilogue, fold_round

    driver = fold_round(scheme_of(bits), dim, entry_of(entry), chunk)
    nbytes = int(np.prod(driver.acc_shape)) * np.dtype(driver.acc_dtype).itemsize
    assert nbytes <= 3_200_000 and not epilogue.device_road(nbytes)


def test_the_silo_accumulator_takes_the_device_road():
    from sda_tpu.parallel import epilogue, fold_round, sumfirst

    driver = fold_round(scheme_of(60), 109_482_240, sumfirst.value_limb_sums_flat, 2)
    assert driver.acc_shape == (2, 2, 153_275_136) and driver.acc_dtype == "uint32"
    assert epilogue.device_road(int(np.prod(driver.acc_shape)) * 4)


def test_the_driver_counts_the_road_each_epilogue_took(monkeypatch):
    import jax

    from sda_tpu.parallel import epilogue, fold_round, sumfirst

    scheme = scheme_of(60)
    driver = fold_round(scheme, 640, sumfirst.value_limb_sums_flat, 4)
    x = rows_of(scheme, 640, 4, 9)
    acc = driver.fold_chunks([x], jax.random.key(1))
    before = roads()
    host = driver.clerk_sums(acc)
    assert isinstance(host, np.ndarray)
    assert roads().get("host", 0) == before.get("host", 0) + 1
    monkeypatch.setattr(epilogue, "DEVICE_ROAD_BYTES", acc.nbytes)
    device = driver.clerk_sums(acc)
    assert isinstance(device, jax.Array) and np.array_equal(np.asarray(device), host)
    assert roads().get("device", 0) == before.get("device", 0) + 1
    # both roads reveal the same canonical int64 aggregate on the host
    clerks = list(range(7))
    assert np.array_equal(driver.reveal(device, clerks), driver.reveal(host, clerks))
