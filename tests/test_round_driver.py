"""The round driver (``sda_tpu.parallel.round``): it pairs a chunk entry with
its accumulate rule and its epilogue and adds no arithmetic, bit for bit
against the hand-written chunk loop; its host feed folds every row of
host blocks once, with a bounded number of blocks alive on the device; and
under a masking scheme its step is the mask stage in front of the entry, whose
seeds and counts both folds hand on beside the accumulator, for the slack
check and the recipient's unmasking."""

import functools
import weakref

import numpy as np
import pytest

from sda_tpu import telemetry
from sda_tpu.ops import find_packed_parameters
from sda_tpu.ops.jaxcfg import ensure_x64
from sda_tpu.protocol import ChaChaMasking, FullMasking, PackedShamirSharing

ensure_x64()

import jax
import jax.numpy as jnp
from jax import lax

from sda_tpu.crypto.masking import ChaChaMasker
from sda_tpu.parallel import FoldRound, engine, fold_round, limb_pallas, limbmatmul, sumfirst
from sda_tpu.parallel.masked import masked_chunk

DIM, CHUNK = 62, 6
K, T, N = 5, 2, 8
FED = ("sda_fabric_fed_blocks_total", "sda_fabric_fed_rows_total", "sda_fabric_fed_bytes_total")
FED_SEEDS = "sda_fabric_fed_seeds_total"
#: a round with no masking scheme, and one under the upstream's ChaCha masking
MASKINGS = [pytest.param(False, id="plain"), pytest.param(True, id="chacha")]


@pytest.fixture(autouse=True)
def device_combine(monkeypatch):
    """At these sizes the recipient would sum the masks on the host; the
    driver's ``unmask`` is about the device fold."""
    monkeypatch.setattr(ChaChaMasker, "DEVICE_COMBINE_THRESHOLD", 1)


@functools.lru_cache(maxsize=None)
def scheme_of(bits):
    p, w2, w3 = find_packed_parameters(K, T, N, min_modulus_bits=bits, seed=0)
    return PackedShamirSharing(K, N, T, p, w2, w3)


def masking_of(bits, dim=DIM, seed_bitsize=128):
    return ChaChaMasking(scheme_of(bits).prime_modulus, dim, seed_bitsize)


def driver_of(bits, entry, masked, dim=DIM):
    """The round of ``entry`` at ``bits``, under ChaCha masking if ``masked``."""
    return fold_round(
        scheme_of(bits), dim, entry, CHUNK, masking=masking_of(bits, dim) if masked else None
    )


def accumulator(folded):
    """Of what a fold returned, masked or not."""
    return folded[0] if isinstance(folded, tuple) else folded


def aggregate_of(driver, folded):
    """The round's aggregate from what a fold returned: the reveal, and under
    a masking scheme the slack check and the recipient's unmasking."""
    revealed = driver.reveal(driver.clerk_sums(accumulator(folded)), range(7))
    if driver.masking is None:
        return revealed
    _acc, seeds, counts = folded
    assert driver.short_windows(counts) == 0
    return driver.unmask(revealed, uploads_of(seeds), chunk=CHUNK)


def uploads_of(seeds):
    """The steps' seeds as a recipient receives them: one vector of int64
    words a participant."""
    return list(np.concatenate([np.asarray(s) for s in seeds]).astype(np.int64))


def fed_seeds():
    values = {c["name"]: c["value"] for c in telemetry.snapshot(0)["counters"]}
    return values.get(FED_SEEDS, 0)


def rows_of(driver, rows, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, driver.modulus, size=(rows, DIM)).astype(driver.input_dtype)


def column_sums(rows, p):
    return np.array([sum(int(v) for v in rows[:, j]) % p for j in range(rows.shape[1])])


def fed():
    values = {c["name"]: c["value"] for c in telemetry.snapshot(0)["counters"]}
    return [values.get(name, 0) for name in FED]


def in_flight_max():
    (gauge,) = [
        g for g in telemetry.snapshot(0)["gauges"] if g["name"] == "sda_fabric_feed_in_flight_max"
    ]
    return gauge["value"]


PALLAS = functools.partial(limb_pallas.share_combine_limb_pallas, interpret=True)
ENTRIES = [
    pytest.param(60, sumfirst.value_limb_sums_chunk, "sum", id="w61-sumfirst-sum"),
    pytest.param(30, sumfirst.value_limb_sums_chunk, "sum", id="w31-sumfirst-sum"),
    pytest.param(30, engine.share_combine_limb, "sum_mod_p", id="w31-participant-sum_mod_p"),
    pytest.param(60, engine.share_combine_limb, "sum_mod_p", id="w61-participant-sum_mod_p"),
    pytest.param(30, PALLAS, "sum_mod_p", id="w31-pallas-sum_mod_p"),
    pytest.param(30, engine.share_combine_limb_xla, "sum_mod_p", id="w31-xla-sum_mod_p"),
]


@pytest.mark.parametrize("bits,entry,accumulate", ENTRIES)
def test_driver_is_the_hand_written_chunk_loop_bit_for_bit(bits, entry, accumulate):
    """The loop every caller wrote for itself (``benchmark/rounds/packed_fold.py``
    still does): entry on the key with the step's number folded in, ``+``,
    ``rem p`` where the entry's partials need it, then the entry's epilogue."""
    scheme = scheme_of(bits)
    driver = fold_round(scheme, DIM, entry, CHUNK)
    assert isinstance(driver, FoldRound) and driver.accumulate == accumulate
    p, plan = scheme.prime_modulus, engine.make_plan(scheme, DIM)
    rows = rows_of(driver, 4 * CHUNK)
    chunks = [jnp.asarray(rows[i : i + CHUNK]) for i in range(0, len(rows), CHUNK)]
    key = jax.random.key(11)

    acc = 0
    for i, chunk in enumerate(chunks):
        acc = acc + entry(chunk, jax.random.fold_in(key, i), plan)
        if accumulate == "sum_mod_p":
            acc = lax.rem(acc, jnp.int64(p))
    acc = np.asarray(acc)
    if accumulate == "sum":
        clerk_sums = sumfirst.clerk_sums_from_limb_acc(acc, plan)[0]
    else:
        clerk_sums = limbmatmul.limb_recombine_host(acc, p).T

    got = driver.fold_chunks(chunks, key)
    assert got.dtype == jnp.int64 and got.shape == driver.acc_shape
    assert np.array_equal(np.asarray(got), acc)
    assert np.array_equal(np.asarray(driver.zero_acc()), np.zeros_like(acc))
    assert np.array_equal(driver.clerk_sums(got), clerk_sums)
    want = column_sums(rows, p)
    assert np.array_equal(driver.reveal(clerk_sums, range(7)), want)
    assert np.array_equal(driver.reveal(clerk_sums, range(1, 8)), want)


@pytest.mark.parametrize("bits,entry,accumulate", ENTRIES[:3])
@pytest.mark.parametrize("in_flight", [1, 2, 3])
def test_feed_of_host_blocks_is_the_fold_of_the_same_rows_resident(
    bits, entry, accumulate, in_flight
):
    """Four blocks and then a shorter fifth: with two or three in flight the
    last round of blocks is a short one."""
    driver = fold_round(scheme_of(bits), DIM, entry, CHUNK)
    rows = rows_of(driver, 9 * CHUNK, seed=in_flight)
    key = jax.random.key(5)
    resident = driver.fold_chunks(
        [jnp.asarray(rows[i : i + CHUNK]) for i in range(0, len(rows), CHUNK)], key
    )
    edges = [0, 2, 4, 6, 8, 9]
    blocks = [rows[a * CHUNK : b * CHUNK] for a, b in zip(edges, edges[1:])]
    got = driver.fold_host_rows(iter(blocks), key, in_flight=in_flight)
    assert np.array_equal(np.asarray(got), np.asarray(resident))
    assert in_flight_max() == min(in_flight, len(blocks))


@pytest.mark.parametrize("masked", MASKINGS)
def test_every_row_is_folded_exactly_once(masked):
    """A cohort of distinct one-hot rows reveals all ones: a row folded twice
    shows as a 2, a row dropped as a 0; masked, so does a row whose seed the
    recipient got twice or not at all."""
    driver = driver_of(60, sumfirst.value_limb_sums_chunk, masked, dim=60)
    rows = np.eye(60, dtype=np.int64)
    blocks = [rows[i : i + 12] for i in range(0, 60, 12)]
    folded = driver.fold_host_rows(blocks, jax.random.key(1), in_flight=2)
    assert np.array_equal(aggregate_of(driver, folded), np.ones(60, dtype=np.int64))


@pytest.mark.parametrize("masked", MASKINGS)
@pytest.mark.parametrize("in_flight", [1, 2, 3, 7])
def test_feed_keeps_to_its_bound_and_counts_what_it_fed(in_flight, masked, monkeypatch):
    driver = driver_of(60, sumfirst.value_limb_sums_chunk, masked)
    blocks = [rows_of(driver, 2 * CHUNK, seed=i) for i in range(5)]
    put = jax.device_put
    held, kept = [], []

    def counted_put(what, *args, **kwargs):
        # the chunks put before and still referenced: those crossing, at most
        held.append(sum(ref() is not None for ref in kept))
        assert what.shape == (CHUNK, DIM) and what.base is not None, "a view of the host block"
        out = put(what, *args, **kwargs)
        kept.append(weakref.ref(out))
        return out

    monkeypatch.setattr(jax, "device_put", counted_put)
    telemetry.reset()
    folded = driver.fold_host_rows(blocks, jax.random.key(2), in_flight=in_flight)
    monkeypatch.undo()
    # one device_put a chunk; a landed chunk is let go of before the next put
    assert len(held) == 2 * len(blocks) and max(held) <= 1, held
    nbytes = sum(block.nbytes for block in blocks)
    assert fed() == [len(blocks), 5 * 2 * CHUNK, nbytes]
    # a seed for every row of a masked step, and none where nothing is masked
    assert fed_seeds() == (5 * 2 * CHUNK if masked else 0)
    assert in_flight_max() == min(in_flight, len(blocks))
    # every step's dispatch is a span behind its put, numbered in step order
    dispatches = telemetry.spans("fabric.step.dispatch")
    assert [s["attrs"] for s in dispatches] == [{"step": i} for i in range(2 * len(blocks))]
    put_spans = telemetry.spans("fabric.feed.put")
    assert [s["attrs"] for s in put_spans] == [
        {"rows": CHUNK, "bytes": blocks[0].nbytes // 2}
    ] * (2 * len(blocks))
    # the host waits once for every block beyond the bound, on the oldest
    waits = telemetry.spans("fabric.feed.wait")
    assert len(waits) == max(0, len(blocks) - in_flight)
    assert all(s["attrs"] == {"on": "in_flight"} for s in waits)
    want = column_sums(np.concatenate(blocks), driver.modulus)
    assert np.array_equal(aggregate_of(driver, folded), want)
    # a second call starts from nothing: the gauge is the last call's
    again = driver.fold_host_rows(blocks[:1], jax.random.key(3), in_flight=in_flight)
    assert in_flight_max() == 1 and fed()[0] == len(blocks) + 1
    if masked:  # and so are the seeds it hands on: this call's two steps'
        assert [s.shape for s in again[1]] == [(CHUNK, 4)] * 2


class LateChunk:
    """A device chunk that lands only when someone waits for it."""

    def __init__(self, nbytes, landed=False):
        self.nbytes, self.landed, self.waited = nbytes, landed, False

    def is_ready(self):
        return self.landed

    def block_until_ready(self):
        self.landed = self.waited = True


def test_the_link_carries_a_bounded_number_of_bytes_at_once():
    """The feed's second bound: before a put that would make more than the
    link's bytes cross at once, the host waits for the oldest crossing chunk
    to land, and for no more of them than it must; a landed chunk is let go
    of without a wait."""
    from sda_tpu.parallel import round as round_module

    assert round_module.LINK_BYTES == 3_200_000_000  # three quarters of the 4 GiB pool
    telemetry.reset()
    crossing = round_module._Crossing(limit=10)
    chunks = [LateChunk(4) for _ in range(5)]
    for chunk in chunks[:2]:
        crossing.make_room(chunk.nbytes)
        crossing.add(chunk)
    assert crossing.nbytes == 8 and not telemetry.spans("fabric.feed.wait")
    crossing.make_room(4)  # 12 would cross: the oldest has to land first
    crossing.add(chunks[2])
    assert [c.waited for c in chunks] == [True, False, False, False, False]
    assert crossing.nbytes == 8 and list(crossing.chunks) == chunks[1:3]
    chunks[1].landed = True  # landed meanwhile: let go of, nobody waits
    crossing.make_room(4)
    crossing.add(chunks[3])
    assert not chunks[1].waited and list(crossing.chunks) == chunks[2:4]
    crossing.make_room(10)  # a put as large as the bound waits for all before it
    assert crossing.nbytes == 0 and chunks[2].waited and chunks[3].waited
    crossing.make_room(25)  # and one larger than the bound goes when the link is clear
    assert [s["attrs"] for s in telemetry.spans("fabric.feed.wait")] == [{"on": "link"}] * 3


def test_the_feed_waits_for_the_link_where_the_bound_is_small(monkeypatch):
    """The same accumulator under a link bound of one chunk: every chunk is
    let go of before the next is put."""
    from sda_tpu.parallel import round as round_module

    driver = fold_round(scheme_of(60), DIM, sumfirst.value_limb_sums_chunk, CHUNK)
    blocks = [rows_of(driver, 2 * CHUNK, seed=i) for i in range(3)]
    key = jax.random.key(9)
    want = np.asarray(driver.fold_host_rows(blocks, key, in_flight=3))
    monkeypatch.setattr(round_module, "LINK_BYTES", blocks[0].nbytes // 2)
    assert np.array_equal(np.asarray(driver.fold_host_rows(blocks, key, in_flight=3)), want)


def test_a_call_of_the_feed_is_one_span_with_its_puts_and_waits_inside():
    """``fabric.feed``: the program's own ``dispatch``, one a call, on the
    clock its puts and waits are on, so that the call less what nests inside it
    is the host's own seconds; ``bytes`` is what the call put."""
    from sda_tpu.telemetry import flight

    driver = fold_round(scheme_of(60), DIM, sumfirst.value_limb_sums_chunk, CHUNK)
    blocks = [rows_of(driver, 2 * CHUNK, seed=i) for i in range(4)]
    telemetry.reset()
    calls = []
    for key, some in ((1, blocks), (2, blocks[:3])):
        fed_before = fed()[2]
        driver.fold_host_rows(some, jax.random.key(key), in_flight=2)
        calls.append(fed()[2] - fed_before)
    feeds = [s for s in telemetry.spans("fabric.feed") if s["name"] == "fabric.feed"]
    assert [s["attrs"] for s in feeds] == [
        {"in_flight": 2, "masked": False, "bytes": nbytes} for nbytes in calls
    ]
    assert calls == [sum(b.nbytes for b in blocks), sum(b.nbytes for b in blocks[:3])]
    inner = [s for s in telemetry.spans("fabric.feed.") if s["name"] != "fabric.feed"]
    inner += telemetry.spans("fabric.step.dispatch")  # one behind every put
    assert len(telemetry.spans("fabric.step.dispatch")) == 2 * (4 + 3)
    waits = [s for s in inner if s["name"] == "fabric.feed.wait"]
    assert [s["attrs"] for s in waits] == [{"on": "in_flight"}] * (2 + 1)  # blocks beyond the bound
    inside = 0
    for call in feeds:
        begin, end = call["start_mono"], call["start_mono"] + call["duration_s"]
        mine = [s for s in inner if begin <= s["start_mono"] < end]
        assert all(s["start_mono"] + s["duration_s"] <= end for s in mine)
        puts = [s for s in mine if s["name"] == "fabric.feed.put"]
        assert sum(s["attrs"]["bytes"] for s in puts) == call["attrs"]["bytes"]
        inside += len(mine)
    assert inside == len(inner), "a put or a wait outside every call"
    # the flight recorder's own seconds of the call: what the puts and the waits leave
    names = flight.interval_report(
        telemetry.spans("fabric.feed") + telemetry.spans("fabric.step.dispatch")
    )["names"]
    assert names["fabric.step.dispatch"]["count"] == 2 * (4 + 3)
    nested = sum(row["seconds"] for name, row in names.items() if name != "fabric.feed")
    assert names["fabric.feed"]["own_s"] == pytest.approx(names["fabric.feed"]["seconds"] - nested)
    assert names["fabric.feed"]["own_s"] > 0 and names["fabric.feed"]["count"] == 2
    # with telemetry off the feed folds the same and records nothing
    telemetry.set_enabled(False)
    try:
        acc = driver.fold_host_rows(blocks, jax.random.key(1), in_flight=2)
    finally:
        telemetry.set_enabled(True)
    recorded = telemetry.spans("fabric.feed") + telemetry.spans("fabric.step.dispatch")
    assert len(recorded) == len(feeds) + len(inner)
    want = column_sums(np.concatenate(blocks), driver.modulus)
    assert np.array_equal(driver.reveal(driver.clerk_sums(acc), range(7)), want)


@pytest.mark.parametrize("masked", MASKINGS)
def test_nothing_is_kept_from_one_call_to_the_next(masked):
    """The same host arrays, changed in place between two calls: the second
    call's aggregate is of what they hold then (and, masked, unmasked from
    the seeds that call handed on, and no others)."""
    driver = driver_of(60, sumfirst.value_limb_sums_chunk, masked)
    blocks = [rows_of(driver, CHUNK, seed=i) for i in range(3)]
    for round_number in range(2):
        folded = driver.fold_host_rows(blocks, jax.random.key(round_number), in_flight=2)
        want = column_sums(np.concatenate(blocks), driver.modulus)
        assert np.array_equal(aggregate_of(driver, folded), want)
        blocks[1][3] = rows_of(driver, 1, seed=99)[0]


@pytest.mark.parametrize("block,in_flight,match", [
    (np.zeros((CHUNK + 1, DIM), np.int64), 1, "multiple of the chunk"),
    (np.zeros((0, DIM), np.int64), 1, "multiple of the chunk"),
    (np.zeros((CHUNK, DIM + 1), np.int64), 1, "a block is"),
    (np.zeros((CHUNK, DIM), np.int32), 1, "int64"),
    (np.zeros((CHUNK, DIM), np.int64), 0, "in_flight"),
])
def test_feed_refuses_what_it_cannot_fold_exactly(block, in_flight, match):
    driver = fold_round(scheme_of(60), DIM, sumfirst.value_limb_sums_chunk, CHUNK)
    with pytest.raises(ValueError, match=match):
        driver.fold_host_rows([block], jax.random.key(0), in_flight=in_flight)


def test_an_entry_nobody_paired_is_refused():
    with pytest.raises(ValueError, match="no accumulate rule"):
        fold_round(scheme_of(60), DIM, lambda secrets, key, plan: secrets, CHUNK)
    with pytest.raises(ValueError, match="at least one row"):
        fold_round(scheme_of(60), DIM, sumfirst.value_limb_sums_chunk, 0)


def test_the_steps_scopes_are_the_entrys_own():
    """The driver wraps the entry in nothing: the compiled step names
    ``fabric.input`` and ``fabric.rand`` and no scope of the driver's."""
    driver = fold_round(scheme_of(60), DIM, sumfirst.value_limb_sums_chunk, CHUNK)
    chunk = jnp.zeros((CHUNK, DIM), jnp.int64)
    text = driver.step.lower(driver.zero_acc(), chunk, jax.random.key(0), np.int32(0)).as_text(
        debug_info=True
    )
    assert "fabric.input/limb_sum" in text and "fabric.rand/draw" in text
    assert "fabric.feed" not in text


# ---------------------------------------------------------------------------
# The masked round: a step that hands on more than an accumulator
# ---------------------------------------------------------------------------

MASKED_ENTRIES = [
    pytest.param(60, sumfirst.value_limb_sums_chunk, "sum", id="w61-sumfirst-sum"),
    pytest.param(30, sumfirst.value_limb_sums_chunk, "sum", id="w31-sumfirst-sum"),
    pytest.param(30, engine.share_combine_limb, "sum_mod_p", id="w31-participant-sum_mod_p"),
]


def hand_written_masked_step(bits, entry, accumulate):
    """The masked chunk step as ``benchmark/rounds/masked_fold._masked_step``
    writes it by hand: ``masked_chunk`` over the entry on the round's key with
    the step's number folded in, then the accumulate rule."""
    scheme = scheme_of(bits)
    chunk_fn = masked_chunk(entry, engine.make_plan(scheme, DIM), masking_of(bits))

    def masked_step(acc, chunk, key, i):
        out, seeds, counts = chunk_fn(chunk, jax.random.fold_in(key, i))
        acc = acc + out
        if accumulate == "sum_mod_p":
            acc = lax.rem(acc, jnp.int64(scheme.prime_modulus))
        return acc, seeds, counts

    return jax.jit(masked_step)


@pytest.mark.parametrize("bits,entry,accumulate", MASKED_ENTRIES)
def test_masked_feed_is_the_resident_masked_fold_and_the_hand_written_loop(bits, entry, accumulate):
    """Accumulator, seeds and counts, bit for bit, three ways: the feed over
    host blocks, ``fold_chunks`` over the same rows resident, and the loop a
    caller wrote by hand around ``masked_chunk``; under either accumulate rule."""
    driver = driver_of(bits, entry, masked=True)
    assert driver.accumulate == accumulate and driver.step.__name__ == "masked_step"
    rows = rows_of(driver, 7 * CHUNK, seed=bits)
    chunks = [jnp.asarray(rows[i : i + CHUNK]) for i in range(0, len(rows), CHUNK)]
    key = jax.random.key(13)

    step, acc, by_hand = hand_written_masked_step(bits, entry, accumulate), driver.zero_acc(), []
    for i, chunk in enumerate(chunks):
        acc, seeds, counts = step(acc, chunk, key, np.int32(i))
        by_hand.append((seeds, counts))

    edges = [0, 3, 5, 7]
    blocks = [rows[a * CHUNK : b * CHUNK] for a, b in zip(edges, edges[1:])]
    fed_fold = driver.fold_host_rows(iter(blocks), key, in_flight=2)
    resident = driver.fold_chunks(chunks, key)
    for got_acc, got_seeds, got_counts in (fed_fold, resident):
        assert np.array_equal(np.asarray(got_acc), np.asarray(acc))
        assert len(got_seeds) == len(got_counts) == len(chunks)
        for (seeds, counts), got_s, got_c in zip(by_hand, got_seeds, got_counts):
            assert isinstance(got_s, jax.Array) and isinstance(got_c, jax.Array)
            assert got_s.shape == (CHUNK, 4) and got_s.dtype == jnp.uint32
            assert got_c.shape == (CHUNK,) and got_c.dtype == jnp.int32
            assert np.array_equal(got_s, seeds) and np.array_equal(got_c, counts)


@pytest.mark.parametrize("bits,entry,accumulate", MASKED_ENTRIES)
def test_reveal_then_unmask_is_the_plain_sum_and_the_reveal_alone_is_not(bits, entry, accumulate):
    """The clerks' sums are of masked values; the recipient unmasks from the
    seeds alone, whatever order they arrive in, from either clerk subset."""
    driver = driver_of(bits, entry, masked=True)
    rows = rows_of(driver, 4 * CHUNK, seed=1)
    blocks = [rows[: 2 * CHUNK], rows[2 * CHUNK :]]
    acc, seeds, counts = driver.fold_host_rows(blocks, jax.random.key(4), in_flight=1)
    want = column_sums(rows, driver.modulus)
    clerk_sums = driver.clerk_sums(acc)
    masked = driver.reveal(clerk_sums, range(7))
    assert not np.array_equal(masked, want)
    assert np.array_equal(driver.reveal(clerk_sums, range(1, 8)), masked)
    assert driver.short_windows(counts) == 0
    uploads = uploads_of(seeds)
    assert len(uploads) == len(rows) and uploads[0].dtype == np.int64
    assert np.array_equal(driver.unmask(masked, uploads, chunk=CHUNK), want)
    assert np.array_equal(driver.unmask(masked, uploads[::-1]), want)
    # a seed short, and the masks do not cancel
    assert not np.array_equal(driver.unmask(masked, uploads[1:], chunk=CHUNK), want)


def test_the_unmasked_aggregate_matches_the_plain_references():
    """Against the benchmark's references, which import nothing of the
    program: the aggregate a python-integer sum, the masks' sum
    ``benchmark/reference_chacha.py`` over the seeds the feed handed on."""
    from benchmark import reference_chacha

    driver = driver_of(60, sumfirst.value_limb_sums_chunk, masked=True)
    rows = rows_of(driver, 2 * CHUNK, seed=8)
    acc, seeds, _counts = driver.fold_host_rows([rows], jax.random.key(6), in_flight=1)
    masked = driver.reveal(driver.clerk_sums(acc), range(7))
    words = np.concatenate([np.asarray(s) for s in seeds])
    mask = reference_chacha.mask_sum(words, DIM, driver.modulus)
    want = column_sums(rows, driver.modulus)
    assert np.array_equal(np.mod(masked - mask, driver.modulus), want)
    assert np.array_equal(driver.unmask(masked, uploads_of(seeds)), want)


def test_the_seeds_are_one_a_row_in_row_order_and_none_repeats():
    """Row ``r`` of step ``i`` is masked under seed ``r`` of that step's
    seeds: taking one row's seed's expansion off that row alone leaves the
    row; and no seed is drawn twice, across rows or rounds."""
    from sda_tpu.ops.chacha import expand_seed

    driver = driver_of(60, sumfirst.value_limb_sums_chunk, masked=True)
    p = driver.modulus
    rows = rows_of(driver, 4 * CHUNK, seed=3)
    blocks = [rows[: 2 * CHUNK], rows[2 * CHUNK :]]
    seen = []
    for round_number in range(2):
        key = jax.random.key(20 + round_number)
        _acc, seeds, _counts = driver.fold_host_rows(blocks, key, in_flight=2)
        words = np.concatenate([np.asarray(s) for s in seeds])
        assert words.shape == (len(rows), 4)
        seen.extend(map(bytes, words))
        # the cohort with row 7 alone: zeros elsewhere still get their masks,
        # so the aggregate less every mask is row 7, and less all but row 7's
        # mask is row 7 plus the expansion of seed 7
        masked = driver.reveal(driver.clerk_sums(_acc), range(7))
        others = np.delete(words, 7, axis=0).astype(np.int64)
        mask_7 = expand_seed(words[7], DIM, p)
        want = (column_sums(rows, p) + mask_7) % p
        assert np.array_equal(driver.unmask(masked, list(others), chunk=CHUNK), want)
    assert len(set(seen)) == 2 * len(rows), "a seed drawn twice"


def test_the_masked_step_is_c5_maskeds_masked_step_text_for_text():
    """The driver's masked step lowers to the text of the step
    ``benchmark/rounds/masked_fold.py`` builds by hand (``c5-masked``'s), name
    and all (``jit_masked_step``: what the trace calls the module), with the
    mask stage's and the entry's scopes in it; the unmasked step's text holds
    nothing of the mask stage."""
    driver = driver_of(60, sumfirst.value_limb_sums_chunk, masked=True)
    args = (driver.zero_acc(), jnp.zeros((CHUNK, DIM), jnp.int64), jax.random.key(0), np.int32(0))
    lowered = driver.step.lower(*args)
    by_hand = hand_written_masked_step(60, sumfirst.value_limb_sums_chunk, "sum").lower(*args)
    assert lowered.as_text() == by_hand.as_text()
    assert "jit_masked_step" in lowered.as_text()
    text = lowered.as_text(debug_info=True)
    for scope in ("fabric.mask/seed", "fabric.mask/expand", "fabric.mask/add",
                  "fabric.input/limb_sum", "fabric.rand/draw"):
        assert scope in text, scope
    plain = driver_of(60, sumfirst.value_limb_sums_chunk, masked=False)
    assert plain.step.__name__ == "step" and plain.masking is None
    assert "fabric.mask" not in plain.step.lower(*args).as_text(debug_info=True)


def test_a_masked_feed_says_so_and_counts_the_seeds_it_handed_on():
    driver = driver_of(60, sumfirst.value_limb_sums_chunk, masked=True)
    blocks = [rows_of(driver, 2 * CHUNK, seed=i) for i in range(3)]
    telemetry.reset()
    driver.fold_host_rows(blocks, jax.random.key(1), in_flight=2)
    driver.fold_host_rows(blocks[:1], jax.random.key(2), in_flight=2)
    nbytes = blocks[0].nbytes
    assert [s["attrs"] for s in telemetry.spans("fabric.feed") if s["name"] == "fabric.feed"] == [
        {"in_flight": 2, "masked": True, "bytes": 3 * nbytes},
        {"in_flight": 2, "masked": True, "bytes": nbytes},
    ]
    assert fed_seeds() == fed()[1] == 8 * CHUNK


@pytest.mark.parametrize("masking,error", [
    (lambda: masking_of(30), ValueError),  # another modulus than the plan's
    (lambda: masking_of(60, dim=DIM + 1), ValueError),  # another dimension
    (lambda: masking_of(60, seed_bitsize=0), ValueError),
    (lambda: FullMasking(scheme_of(60).prime_modulus), TypeError),  # not ChaCha
], ids=["modulus", "dimension", "seed_bitsize", "full-masking"])
def test_a_masking_scheme_that_is_not_the_rounds_is_refused(masking, error):
    with pytest.raises(error, match="mask|seed_bitsize"):
        fold_round(scheme_of(60), DIM, sumfirst.value_limb_sums_chunk, CHUNK, masking=masking())


def test_an_unmasked_round_has_nothing_to_check_or_take_off():
    driver = driver_of(60, sumfirst.value_limb_sums_chunk, masked=False)
    with pytest.raises(ValueError, match="no masking scheme"):
        driver.short_windows([np.zeros(CHUNK, np.int32)])
    with pytest.raises(ValueError, match="no masking scheme"):
        driver.unmask(np.zeros(DIM, np.int64), [])
