"""The round driver (``sda_tpu.parallel.round``): it pairs a chunk entry with
its accumulate rule and its epilogue and adds no arithmetic, bit for bit
against the hand-written chunk loop; and its host feed folds every row of
host blocks once, with a bounded number of blocks alive on the device."""

import functools
import weakref

import numpy as np
import pytest

from sda_tpu import telemetry
from sda_tpu.ops import find_packed_parameters
from sda_tpu.ops.jaxcfg import ensure_x64
from sda_tpu.protocol import PackedShamirSharing

ensure_x64()

import jax
import jax.numpy as jnp
from jax import lax

from sda_tpu.parallel import FoldRound, engine, fold_round, limb_pallas, limbmatmul, sumfirst

DIM, CHUNK = 62, 6
K, T, N = 5, 2, 8
FED = ("sda_fabric_fed_blocks_total", "sda_fabric_fed_rows_total", "sda_fabric_fed_bytes_total")


@functools.lru_cache(maxsize=None)
def scheme_of(bits):
    p, w2, w3 = find_packed_parameters(K, T, N, min_modulus_bits=bits, seed=0)
    return PackedShamirSharing(K, N, T, p, w2, w3)


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


def test_every_row_is_folded_exactly_once():
    """A cohort of distinct one-hot rows reveals all ones: a row folded twice
    shows as a 2, a row dropped as a 0."""
    driver = fold_round(scheme_of(60), 60, sumfirst.value_limb_sums_chunk, CHUNK)
    rows = np.eye(60, dtype=np.int64)
    blocks = [rows[i : i + 12] for i in range(0, 60, 12)]
    acc = driver.fold_host_rows(blocks, jax.random.key(1), in_flight=2)
    aggregate = driver.reveal(driver.clerk_sums(acc), range(7))
    assert np.array_equal(aggregate, np.ones(60, dtype=np.int64))


@pytest.mark.parametrize("in_flight", [1, 2, 3, 7])
def test_feed_keeps_to_its_bound_and_counts_what_it_fed(in_flight, monkeypatch):
    driver = fold_round(scheme_of(60), DIM, sumfirst.value_limb_sums_chunk, CHUNK)
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
    acc = driver.fold_host_rows(blocks, jax.random.key(2), in_flight=in_flight)
    monkeypatch.undo()
    # one device_put a chunk; a landed chunk is let go of before the next put
    assert len(held) == 2 * len(blocks) and max(held) <= 1, held
    nbytes = sum(block.nbytes for block in blocks)
    assert fed() == [len(blocks), 5 * 2 * CHUNK, nbytes]
    assert in_flight_max() == min(in_flight, len(blocks))
    put_spans = telemetry.spans("fabric.feed.put")
    assert [s["attrs"] for s in put_spans] == [
        {"rows": CHUNK, "bytes": blocks[0].nbytes // 2}
    ] * (2 * len(blocks))
    # the host waits once for every block beyond the bound, on the oldest
    waits = telemetry.spans("fabric.feed.wait")
    assert len(waits) == max(0, len(blocks) - in_flight)
    assert all(s["attrs"] == {"on": "in_flight"} for s in waits)
    want = column_sums(np.concatenate(blocks), driver.modulus)
    assert np.array_equal(driver.reveal(driver.clerk_sums(acc), range(7)), want)
    # a second call starts from nothing: the gauge is the last call's
    driver.fold_host_rows(blocks[:1], jax.random.key(3), in_flight=in_flight)
    assert in_flight_max() == 1 and fed()[0] == len(blocks) + 1


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
        {"in_flight": 2, "bytes": nbytes} for nbytes in calls
    ]
    assert calls == [sum(b.nbytes for b in blocks), sum(b.nbytes for b in blocks[:3])]
    inner = [s for s in telemetry.spans("fabric.feed.") if s["name"] != "fabric.feed"]
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
    names = flight.interval_report(telemetry.spans("fabric.feed"))["names"]
    nested = sum(row["seconds"] for name, row in names.items() if name != "fabric.feed")
    assert names["fabric.feed"]["own_s"] == pytest.approx(names["fabric.feed"]["seconds"] - nested)
    assert names["fabric.feed"]["own_s"] > 0 and names["fabric.feed"]["count"] == 2
    # with telemetry off the feed folds the same and records nothing
    telemetry.set_enabled(False)
    try:
        acc = driver.fold_host_rows(blocks, jax.random.key(1), in_flight=2)
    finally:
        telemetry.set_enabled(True)
    assert len(telemetry.spans("fabric.feed")) == len(feeds) + len(inner)
    want = column_sums(np.concatenate(blocks), driver.modulus)
    assert np.array_equal(driver.reveal(driver.clerk_sums(acc), range(7)), want)


def test_nothing_is_kept_from_one_call_to_the_next():
    """The same host arrays, changed in place between two calls: the second
    call's aggregate is of what they hold then."""
    driver = fold_round(scheme_of(60), DIM, sumfirst.value_limb_sums_chunk, CHUNK)
    blocks = [rows_of(driver, CHUNK, seed=i) for i in range(3)]
    for round_number in range(2):
        acc = driver.fold_host_rows(blocks, jax.random.key(round_number), in_flight=2)
        want = column_sums(np.concatenate(blocks), driver.modulus)
        assert np.array_equal(driver.reveal(driver.clerk_sums(acc), range(7)), want)
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
