"""The few-fat-rows round: packed Shamir, no masking, a cross-silo cohort of a
few resident rows, each a whole model's update of 10⁸ coordinates.

The clock runs from key to comparison: fresh share key -> ``dispatch``: the
program's round driver (``FoldRound.fold_chunks``) over the resident chunks ->
``fold``: ``block_until_ready`` on the accumulator -> ``epilogue``: the
driver's clerk sums and its reveal from exactly ``reconstruction_threshold``
clerks, the road both take chosen by the driver from the accumulator's size
(at this size the device's: the clerk sums and the aggregate are computed
on the chip and the aggregate crosses to the host as uint32 words) ->
``check``: the whole aggregate, every column, compared with the plain
reference (:mod:`benchmark.reference_silo`), and a strided sample of the clerk
sums fetched for the harness's comparison of consecutive rounds.

**The round binds the program's driver**, as the host-fed rounds do: its
traffic file names, by dotted path, the driver's factory (``driver``), the
chunk entry (``engine``), the sharing scheme's class and the parameter search
(``sharing_scheme``, ``scheme_parameters``) and the reader of the program's
counters (``telemetry``); and as numbers the clerk-sum sample's batches
(``sample_batches``). This file imports nothing of the program.

What only this round knows goes into ``compared()``, with limit 0:

``host_road_epilogues``
    the rounds run less the epilogues the program counted on the device road
    (``sda_fabric_epilogue_roads_total{road="device"}``) in them, at least 0:
    the cell measures the device road, every round.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import reference_silo
from benchmark import traffic as traffic_mod
from benchmark.harness import HarnessError

#: the spans a round opens inside the harness's ``round``
span_names = ("dispatch", "fold", "epilogue", "check")

#: what the round reads of its traffic file beyond the generator's fields
TRAFFIC_KEYS = (
    "driver", "engine", "sharing_scheme", "scheme_parameters", "telemetry", "sample_batches",
)

ROADS = "sda_fabric_epilogue_roads_total"


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
        raise HarnessError(f"{tr.name}: the few-fat-rows round runs on one chip, one pass a round")
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


def _maker(cell, modulus: int, devices):
    """The jitted ``make(key, i, hi, lo) -> (chunk, hi, lo, columns)``: chunk
    ``i`` of the resident rows, ``(chunk, dim)`` seeded uniform values over
    ``[0, 2^(bits(p)-1))`` (canonical, zero bias); the reference's running
    column sums as uint32 words with this chunk added; the chunk's strided
    columns for the host's own sums. One compile."""
    import jax
    import jax.numpy as jnp

    tr, dim = cell.traffic, cell.dim
    nbits = int(modulus).bit_length() - 1
    dtype = traffic_mod.input_dtype(modulus)
    bits_dtype = jnp.uint32 if nbits <= 32 else jnp.uint64
    mask = bits_dtype((1 << nbits) - 1)

    def make(key, i, hi, lo):
        bits = jax.random.bits(jax.random.fold_in(key, i), (tr.chunk, dim), dtype=bits_dtype)
        chunk = (bits & mask).astype(dtype)
        hi, lo = reference_silo.add_rows((hi, lo), chunk)
        return chunk, hi, lo, reference_silo.strided_columns(chunk)

    one = traffic_mod.replicated(devices, None)
    return jax.jit(make, out_shardings=(one, one, one, one))


def _sampler(driver, batches: int):
    """The jitted ``sample(clerk_sums) -> (n, ≥ batches)``: a strided sample
    of every clerk's sums, what the harness compares between rounds."""
    import jax

    stride = max(1, driver.plan.n_batches // batches)

    def sample(clerk_sums):
        return clerk_sums[:, ::stride]

    return jax.jit(sample)


def _reading(snapshot: dict, name: str, **labels):
    """The value of the program's counter ``name`` with ``labels``."""
    return sum(
        series["value"]
        for series in snapshot["counters"]
        if series["name"] == name and all(series["labels"].get(k) == v for k, v in labels.items())
    )


class Session:
    """One cell set up: the rows resident, their reference on the host, the
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
        self.steps_per_round = tr.steps_per_pass
        one = traffic_mod.replicated(self.devices, None)
        self.fold_in = jax.jit(jax.random.fold_in, out_shardings=one)
        self.sample = _sampler(self.driver, int(tr.params["sample_batches"]))
        stage("program")

        make = _maker(cell, self.modulus, self.devices)
        seed_key = jax.random.key(seed)
        input_key = self.fold_in(seed_key, 0)
        self.share_key = self.fold_in(seed_key, 1)
        hi = lo = jax.device_put(jnp.zeros((cell.dim,), jnp.uint32), one)
        self.chunks, columns = [], []
        for i in range(tr.steps_per_pass):
            chunk, hi, lo, strided = make(input_key, jnp.int32(i), hi, lo)
            self.chunks.append(chunk)
            columns.append(strided)
        hi, lo = jax.device_get((hi, lo))
        columns = np.concatenate([np.asarray(c) for c in columns])
        stage("input_on_device")
        self.want = reference_silo.aggregate(hi, lo, columns, self.modulus, tr.rows)
        del hi, lo
        stage("reference_on_host")
        self.chunk_bytes = int(self.chunks[0].nbytes)
        acc_itemsize = np.dtype(self.driver.acc_dtype).itemsize
        self.acc_bytes = int(np.prod(self.driver.acc_shape)) * acc_itemsize
        self.rounds_run = 0
        self.device_at_start = _reading(self.snapshot(0), ROADS, road="device")

    def run_round(self, index: int, spans, subsets=None):
        """One round. Returns ``(matched, clerk-sum sample)``; ``subsets``
        (warm-up only) are further clerk subsets that must reveal the same."""
        driver = self.driver
        with spans("round", index):
            key = self.fold_in(self.share_key, index)
            with spans("dispatch", index):
                acc = driver.fold_chunks(self.chunks, key)
            with spans("fold", index):
                acc.block_until_ready()
            with spans("epilogue", index):
                clerk_sums = driver.clerk_sums(acc)
                del acc  # the accumulator's 2.45e9 B go before the reveal's
                got = driver.reveal(clerk_sums, self.survivors)
            with spans("check", index):
                matched = bool(np.array_equal(got, self.want))
                evidence = np.asarray(self.sample(clerk_sums))
        del got
        for subset in subsets or ():
            matched = matched and bool(np.array_equal(driver.reveal(clerk_sums, subset), self.want))
        self.rounds_run += 1
        return matched, evidence

    def compared(self) -> dict:
        device = _reading(self.snapshot(0), ROADS, road="device") - self.device_at_start
        return {
            "host_road_epilogues": {"value": max(0, self.rounds_run - device), "limit": 0},
        }

    def memory_peak_bytes(self) -> int:
        """The peak on the cell's chip (0 where the backend reports none, as
        the CPU does)."""
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in self.devices]
        return int(max(peaks))


def _described(cell, devices):
    """The round's programs, the rows' maker and the shapes they take,
    placed on ``devices`` (attached or only described). Holds no array."""
    import jax
    import numpy as np

    tr = cell.traffic
    devices = list(devices[: cell.chips])
    driver, survivors, _second = build_driver(cell)
    one = traffic_mod.replicated(devices, None)

    def placed(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    key = jax.eval_shape(lambda: jax.random.key(0))
    key, index = placed(key.shape, key.dtype), placed((), "int32")
    plan = driver.plan
    modulus = int(plan.modulus)
    acc = placed(driver.acc_shape, driver.acc_dtype)
    clerk_sums = placed((plan.share_count, plan.n_batches), "int64")
    rows = placed((len(survivors),), "int32")
    lagrange = placed((4, plan.input_size, len(survivors)), "uint32")
    chunk = placed((tr.chunk, cell.dim), np.dtype(driver.input_dtype))
    words = placed((cell.dim,), "uint32")
    sample = _sampler(driver, int(tr.params["sample_batches"]))
    return {
        "steps": [
            (driver.step, (acc, chunk, key, index)),
            (driver.epilogue_program, (acc,)),
            (driver.reveal_program, (clerk_sums, rows, lagrange)),
            (sample, (clerk_sums,)),
        ],
        "input": (_maker(cell, modulus, devices), (key, index, words, words)),
    }


def steps(cell, devices) -> list:
    """``[(jitted, example arguments)]``: the driver's chunk step, its device
    epilogue, its device reveal and the clerk-sum sample, in that order."""
    return _described(cell, devices)["steps"]


def input_maker(cell, devices) -> tuple:
    """``(jitted, example arguments)`` of the program that makes one chunk of
    the resident rows in set-up, for the compile rehearsal."""
    return _described(cell, devices)["input"]
