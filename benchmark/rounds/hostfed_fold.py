"""The host-fed round: packed Shamir, no masking, a cohort that sits in host
memory and crosses the host link in every round.

Nothing of a round's input is on the chip when the round starts. The clock
runs from key to comparison: fresh share key -> ``dispatch``: the program's
feed (``FoldRound.fold_host_rows``) over the host cohort's blocks, which puts
each block on the device chunk by chunk, a chunk's step dispatched behind it,
with a bounded number of blocks alive and of bytes crossing -> ``fold``:
``block_until_ready`` on the accumulator, the wait for the link and for the
last step -> ``fetch`` -> ``epilogue``: the driver's host epilogue to clerk
sums and its reveal from exactly ``reconstruction_threshold`` clerks ->
``check``: the whole aggregate compared with the plain reference, bit for bit.

**The round binds the program's driver.** Its traffic file names, by dotted
path, the driver's factory (``driver``: ``factory(scheme, dim, entry, chunk)``
-> an object with ``step``, ``plan``, ``input_dtype``, ``acc_shape``,
``fold_host_rows(blocks, key, in_flight=)``, ``clerk_sums(acc)`` and
``reveal(clerk_sums, clerks)``), the chunk entry (``engine``), the sharing
scheme's class and the parameter search (``sharing_scheme``,
``scheme_parameters``) and the reader of the program's counters
(``telemetry``); and as numbers the feed's ``block_rows`` and ``in_flight``.
Who pairs the entry with its accumulate rule and its epilogue is the driver:
there are no adapters here. This file imports nothing of the program.

**The cohort.** Set-up makes it on the device chunk by chunk with the one
generator (:func:`benchmark.traffic.chunk_maker`, which hands back the
reference's half sums and strided columns: :mod:`benchmark.reference` stays
the aggregate's plain reference) and fetches every chunk into its place in a
host block; the device keeps none of it. **Between rounds, outside the
``round`` span, the cohort changes**: one row of every block is overwritten
with fresh values (numpy, from the seed and the round's number) and the
reference's aggregate moves by the difference mod p, in plain numpy. A feed
that kept a block on the device from an earlier round reveals the earlier
cohort's aggregate: ``rounds_mismatched``.

What only this round knows goes into ``compared()``, each with limit 0:

``fed_bytes_short``
    the rounds run times the cohort's bytes, less what the program's counter
    ``sda_fabric_fed_bytes_total`` counted in them (at least 0): every row
    crossed, in every round;
``in_flight_over``
    the most blocks alive at once in any round's feed (the program's gauge
    ``sda_fabric_feed_in_flight_max``, read after every round) less the
    traffic file's ``in_flight``, at least 0.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import reference
from benchmark import traffic as traffic_mod
from benchmark.harness import HarnessError

#: the spans a round opens inside the harness's ``round``
span_names = ("dispatch", "fold", "fetch", "epilogue", "check")

#: what the round reads of its traffic file beyond the generator's fields
TRAFFIC_KEYS = (
    "driver", "engine", "sharing_scheme", "scheme_parameters", "telemetry",
    "block_rows", "in_flight", "fresh_rows_per_block",
)

#: a traced round leaves the profiler about 2.75 GB of host memory (8.8e6
#: events of the chunks' host relayout) and reading the trace 1.6 times that
#: again: on a one-chip v5e host of 40 GiB three traced rounds peaked at
#: 45.1 GB of RSS and four were killed, so two
TRACE_WINDOW_ROUNDS = 2

FED_BYTES = "sda_fabric_fed_bytes_total"
IN_FLIGHT_MAX = "sda_fabric_feed_in_flight_max"


def build_driver(cell):
    """``(driver, survivors, second subset)``: the program's round driver for
    the cell's scheme, dim, chunk entry and chunk; the clerks the round
    reveals from, and the warm-up's second subset. Holds no array."""
    spec = cell.config["scheme"]
    if spec["kind"] != "packed_shamir":
        raise HarnessError(f"unknown scheme kind {spec['kind']!r}")
    tr = cell.traffic
    traffic_mod.require(tr.params, TRAFFIC_KEYS, tr.name)
    calls = tr.params
    if tr.mesh or tr.passes != 1:
        raise HarnessError(f"{tr.name}: the host-fed round runs on one chip, one pass a round")
    if tr.rows % calls["block_rows"] or calls["block_rows"] % tr.chunk or calls["in_flight"] < 1:
        raise HarnessError(
            f"{tr.name}: rows are whole blocks, a block whole chunks, in_flight at least 1"
        )
    k, t, n = spec["secret_count"], spec["privacy_threshold"], spec["share_count"]
    p, w2, w3 = traffic_mod.resolve(calls["scheme_parameters"])(
        k, t, n, min_modulus_bits=spec["min_modulus_bits"], seed=spec["parameter_seed"]
    )
    scheme = traffic_mod.resolve(calls["sharing_scheme"])(k, n, t, p, w2, w3)
    driver = traffic_mod.resolve(calls["driver"])(
        scheme, cell.dim, traffic_mod.resolve(calls["engine"]), tr.chunk
    )
    threshold = scheme.reconstruction_threshold
    stated = cell.config["guarantees"]
    if stated["reconstruction_threshold"] != threshold or stated["privacy_threshold"] != t:
        raise HarnessError("the configuration's stated thresholds are not the scheme's")
    dropped = set(cell.config["dropped_clerks"])
    alive = [i for i in range(n) if i not in dropped]
    if len(alive) < threshold:
        raise HarnessError("fewer clerks survive than reconstruction needs")
    survivors = alive[:threshold]
    return driver, survivors, [i for i in range(n) if i != survivors[-1]][:threshold]


def _reading(snapshot: dict, kind: str, name: str):
    """The value of the program's series ``name`` in a telemetry snapshot."""
    return sum(series["value"] for series in snapshot[kind] if series["name"] == name)


class Session:
    """One cell set up: the cohort in host memory, its reference, the
    program's driver ready, to run rounds."""

    def __init__(self, cell, seed: int, devices, stages=None):
        """``stages``, if given, is filled with the seconds each part of
        set-up took, for the run's record."""
        import jax
        import jax.numpy as jnp

        clock = time.perf_counter()
        stages = {} if stages is None else stages

        def stage(name):
            nonlocal clock
            now = time.perf_counter()
            stages[name] = now - clock
            clock = now

        if len(devices) < cell.chips:
            raise HarnessError(
                f"cell {cell.name!r} needs {cell.chips} devices, got {len(devices)}"
            )
        self.cell = cell
        self.devices = list(devices[: cell.chips])
        tr = cell.traffic
        self.driver, self.survivors, second = build_driver(cell)
        self.warmup_subsets = [second]
        self.snapshot = traffic_mod.resolve(tr.params["telemetry"])
        self.plan, self.modulus = self.driver.plan, int(self.driver.plan.modulus)
        if self.modulus >= 1 << 62:
            raise HarnessError("the fresh rows' update of the aggregate is int64: p < 2^62")
        self.in_flight = int(tr.params["in_flight"])
        self.fresh_rows_per_block = int(tr.params["fresh_rows_per_block"])
        block_rows = int(tr.params["block_rows"])
        self.seed = seed
        self.steps_per_round = tr.steps_per_pass
        everywhere = traffic_mod.replicated(self.devices, None)
        self.fold_in = jax.jit(jax.random.fold_in, out_shardings=everywhere)
        stage("program")

        # the cohort and the reference's sums of it, made on the device from
        # the seed by one program, chunk by chunk; each chunk goes to its
        # place in a host block and leaves the device
        make = traffic_mod.chunk_maker(tr, cell.dim, self.modulus, self.devices, None)
        seed_key = jax.random.key(seed)
        input_key = self.fold_in(seed_key, 0)
        self.share_key = self.fold_in(seed_key, 1)
        half_sums = jax.device_put(jnp.zeros((2, cell.dim), jnp.int64), everywhere)
        dtype = self.driver.input_dtype
        self.blocks = [
            np.empty((block_rows, cell.dim), dtype) for _ in range(tr.rows // block_rows)
        ]
        # a 64-bit array comes back to the host at a sixteenth of a 32-bit
        # one's rate (0.21 against 3.3 GB/s: chip run, PR 34): as two words
        words = jax.jit(
            lambda c: ((c & 0xFFFFFFFF).astype(jnp.uint32), (c >> 32).astype(jnp.uint32))
        )
        columns = []
        for i in range(tr.steps_per_pass):
            chunk, half_sums, strided = make(input_key, jnp.int32(i), half_sums)
            block, row = divmod(i * tr.chunk, block_rows)
            place = self.blocks[block][row : row + tr.chunk]
            if dtype.itemsize == 8:
                low, high = (np.asarray(word) for word in words(chunk))
                np.copyto(place, high)
                place <<= 32
                place |= low
            else:
                np.copyto(place, np.asarray(chunk))
            columns.append(np.asarray(strided))
            del chunk
        half_sums = np.asarray(half_sums)
        stage("input_to_host")
        self.want = reference.aggregate(
            half_sums, np.concatenate(columns), self.modulus, tr.passes, tr.rows
        )
        stage("reference_on_host")
        self.round_bytes = sum(block.nbytes for block in self.blocks)
        self.chunk_bytes = tr.chunk * cell.dim * dtype.itemsize
        self.acc_bytes = int(np.prod(self.driver.acc_shape)) * 8  # int64
        self.rounds_run = self.in_flight_most = 0
        self.fed_at_start = _reading(self.snapshot(0), "counters", FED_BYTES)

    def _refresh_rows(self, index: int) -> None:
        """The cohort changes between rounds: in every block,
        ``fresh_rows_per_block`` rows are overwritten with fresh seeded
        values, and the reference's aggregate moves by the difference."""
        rng = np.random.default_rng([self.seed, index])
        top = 1 << (self.modulus.bit_length() - 1)  # the generator's range
        for block in self.blocks:
            for row in rng.integers(0, block.shape[0], size=self.fresh_rows_per_block):
                fresh = rng.integers(0, top, size=block.shape[1], dtype=np.int64)
                moved = self.want + (fresh - block[row].astype(np.int64))
                self.want = np.mod(moved, self.modulus)
                block[row] = fresh.astype(block.dtype)

    def run_round(self, index: int, spans, subsets=None):
        """One round. Returns ``(matched, clerk_sums)``; ``subsets`` (warm-up
        only) are further clerk subsets that must reveal the same."""
        self._refresh_rows(index)
        driver = self.driver
        with spans("round", index):
            key = self.fold_in(self.share_key, index)
            with spans("dispatch", index):
                acc = driver.fold_host_rows(self.blocks, key, in_flight=self.in_flight)
            with spans("fold", index):
                acc.block_until_ready()
            with spans("fetch", index):
                acc_host = np.asarray(acc)
            with spans("epilogue", index):
                clerk_sums = driver.clerk_sums(acc_host)
                got = driver.reveal(clerk_sums, self.survivors)
            with spans("check", index):
                matched = bool(np.array_equal(got, self.want))
        for subset in subsets or ():
            matched = matched and bool(np.array_equal(driver.reveal(clerk_sums, subset), self.want))
        self.rounds_run += 1
        self.in_flight_most = max(
            self.in_flight_most, _reading(self.snapshot(0), "gauges", IN_FLIGHT_MAX)
        )
        return matched, clerk_sums

    def compared(self) -> dict:
        fed = _reading(self.snapshot(0), "counters", FED_BYTES) - self.fed_at_start
        return {
            "fed_bytes_short": {
                "value": max(0, self.rounds_run * self.round_bytes - fed), "limit": 0,
            },
            "in_flight_over": {
                "value": max(0, self.in_flight_most - self.in_flight), "limit": 0,
            },
        }

    def memory_peak_bytes(self) -> int:
        """The peak on the cell's chip (0 where the backend reports none, as
        the CPU does)."""
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in self.devices]
        return int(max(peaks))


def _described(cell, devices):
    """The driver's chunk step, the cohort's maker and the shapes both take,
    placed on ``devices`` (attached or only described). Holds no array."""
    import jax

    tr = cell.traffic
    devices = list(devices[: cell.chips])
    driver, _survivors, _second = build_driver(cell)
    small = traffic_mod.replicated(devices, None)

    def placed(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=small)

    key = jax.eval_shape(lambda: jax.random.key(0))
    key, index = placed(key.shape, key.dtype), placed((), "int32")
    chunk = jax.ShapeDtypeStruct(
        (tr.chunk, cell.dim), driver.input_dtype,
        sharding=traffic_mod.chunk_sharding(devices, None),
    )
    maker = traffic_mod.chunk_maker(tr, cell.dim, int(driver.plan.modulus), devices, None)
    return {
        "step": (driver.step, (placed(driver.acc_shape, "int64"), chunk, key, index)),
        "input": (maker, (key, index, placed((2, cell.dim), "int64"))),
    }


def steps(cell, devices) -> list:
    """``[(jitted, example arguments)]``: the one program the feed runs on
    the device, the driver's chunk step at a ``(chunk, dim)`` chunk (a block
    is put as its chunks: no program slices it)."""
    return [_described(cell, devices)["step"]]


def input_maker(cell, devices) -> tuple:
    """``(jitted, example arguments)`` of the program that makes one chunk of
    the cohort in set-up, for the compile rehearsal."""
    return _described(cell, devices)["input"]
