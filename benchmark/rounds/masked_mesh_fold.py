"""The masked round over a mesh: packed Shamir under the upstream's ChaCha
masking, a resident input sharded by rows over the chips of a mesh, every chip
masking and folding its own rows, the recipient's re-expansion spread over the
same chips.

The clock runs from key to comparison: fresh round key -> ``dispatch``: the
program's masked chunk step over every chunk of the resident input
(``FoldRound.fold_chunks`` of a round built with a masking scheme and a mesh:
on every chip its own rows of the chunk, every row a fresh seed from a key of
the chip's own, the seed's ChaCha expansion added mod p, the masked rows
through the engine the traffic file binds, a limb psum over ``p``), which
hands back the accumulator and every step's seeds and accepted-draw counts,
sharded over the chips that drew them -> ``fold``: ``block_until_ready`` on
the accumulator -> ``fetch``: the accumulator and the counts -> ``epilogue``:
the driver's host epilogue to clerk sums, its slack check over the counts and
its reveal from exactly ``reconstruction_threshold`` clerks: the *masked*
aggregate -> ``unmask``: the seeds fetched to the host as a recipient receives
them (one vector of int64 words a participant) and the driver's ``unmask``,
which puts them sharded over the mesh and folds them ``recipient_chunk`` a
chip a call, every chip expanding its own, the chips' partial sums meeting mod
p -> ``check``: the whole aggregate compared with the plain reference, bit for
bit (:mod:`benchmark.reference`: the masks cancel, so the aggregate's
reference is the plain column sum), and the round's seeds searched for
repeats.

**The round binds the program's driver** by dotted path, as
:mod:`benchmark.rounds.hostfed_masked_fold` does (``driver``, ``engine``,
``sharing_scheme``, ``scheme_parameters``, ``telemetry``, ``masking_scheme``),
and calls its factory with the scheme as ``masking=`` and the traffic file's
mesh as ``mesh=``: who shards the step, folds the mesh position into the key,
keeps the seeds where they were drawn, checks the slack and spreads the
recipient's fold is the driver; there are no adapters here. ``recipient_fold``
is the handle on the recipient's jitted fold over a mesh
(``handle(mesh) -> fn(seeds, dim, modulus, backend) -> (met, parts, counts)``):
for ``steps`` and for the warm-up's comparison of two mask parts. The resident
input is made as :mod:`benchmark.rounds.packed_fold` makes a mesh cell's: the
one generator, every shard on its own chip. This file imports nothing of the
program.

The window holds two device programs: the driver's sharded masked chunk step
and the recipient's sharded fold (``steps`` gives both, the chunk step first).
``compared()``, each with limit 0: ``masked_fold``'s ``unmasked_reveals``,
``slack_exhausted_rows`` and ``mask_parts_mismatched`` (in warm-up, of the
timed fold's per-chip partial sums, the first chip's of the first call and the
last chip's of the last call against :mod:`benchmark.reference_chacha` over
those chips' seeds), and

``seeds_repeated``
    rows of a round's seeds that equal an earlier row of the same round,
    summed over the rounds: what a step whose key is not folded over the mesh
    gives (every chip the same seeds), and what no aggregate can show, since
    equal masks cancel as well as distinct ones;
``seeds_short``
    the rounds run times the round's rows, less the seeds that reached the
    recipient's combine in them (at least 0): the lesser of what this round
    handed to the driver's ``unmask`` and of what the program's counter
    ``sda_crypto_chacha_expands_total`` says its combine took;
``unmask_chips_short``
    the device folds the recipient's combine ran (the program's counter
    ``sda_crypto_chacha_folds_total``; at least the sharded folds the rounds
    need: their rows over ``recipient_chunk`` times the chips) times the
    chips, less the chips those folds ran on
    (``sda_crypto_chacha_fold_chips_total``), at least 0: a combine that fell
    back to one chip reads the chips less one a fold.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import reference, reference_chacha
from benchmark import traffic as traffic_mod
from benchmark.harness import HarnessError
from benchmark.rounds import hostfed_fold, masked_fold

#: the spans a round opens inside the harness's ``round``
span_names = ("dispatch", "fold", "fetch", "epilogue", "unmask", "check")

#: what the round reads of its traffic file beyond the generator's fields
TRAFFIC_KEYS = (
    "driver", "engine", "sharing_scheme", "scheme_parameters", "telemetry",
    "masking_scheme", "recipient_fold", "recipient_chunk",
)

#: the program's counters of its recipient's combine: seeds, folds, chips
COMBINE = (
    "sda_crypto_chacha_expands_total", "sda_crypto_chacha_folds_total",
    "sda_crypto_chacha_fold_chips_total",
)


def build_driver(cell, mesh):
    """``(driver, survivors, second subset)``: the program's round driver for
    the cell's scheme, dim, chunk entry and chunk, under the configuration's
    masking scheme and over ``mesh``; the clerks the round reveals from, and
    the warm-up's second subset. Holds no array."""
    spec = cell.config["scheme"]
    if spec["kind"] != "packed_shamir":
        raise HarnessError(f"unknown scheme kind {spec['kind']!r}")
    tr = cell.traffic
    traffic_mod.require(tr.params, TRAFFIC_KEYS, tr.name)
    calls = tr.params
    if mesh is None or tr.mesh_shape[1] != 1 or tr.passes != 1:
        raise HarnessError(
            f"{tr.name}: the masked mesh round runs over a mesh with d = 1, one pass a round"
        )
    if tr.rows % (int(calls["recipient_chunk"]) * tr.chips):
        raise HarnessError(f"{tr.name}: the recipient's folds are whole: rows over chunk x chips")
    k, t, n = spec["secret_count"], spec["privacy_threshold"], spec["share_count"]
    p, w2, w3 = traffic_mod.resolve(calls["scheme_parameters"])(
        k, t, n, min_modulus_bits=spec["min_modulus_bits"], seed=spec["parameter_seed"]
    )
    scheme = traffic_mod.resolve(calls["sharing_scheme"])(k, n, t, p, w2, w3)
    driver = traffic_mod.resolve(calls["driver"])(
        scheme, cell.dim, traffic_mod.resolve(calls["engine"]), tr.chunk,
        masking=masked_fold._masking(cell, int(p)), mesh=mesh,
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


def _seed_words(driver) -> int:
    """uint32 words of one seed."""
    return (driver.masking.seed_bitsize + 31) // 32


def repeated_rows(seeds) -> int:
    """Rows of ``seeds`` (``(rows, words)`` uint32) that equal an earlier row."""
    seeds = np.ascontiguousarray(seeds)
    whole = seeds.view(np.dtype((np.void, seeds.dtype.itemsize * seeds.shape[1])))
    return int(seeds.shape[0] - np.unique(whole).size)


class Session:
    """One cell set up on its devices: input sharded over the mesh and
    reference resident, the program's masked driver over the mesh ready, to
    run rounds."""

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
        self.mesh = traffic_mod.make_mesh(tr, self.devices)
        # what the program must have is looked up before the input is made: a
        # checkout without the driver's mesh or the sharded fold fails at once
        traffic_mod.require(tr.params, TRAFFIC_KEYS, tr.name)
        self.recipient_fold = traffic_mod.resolve(tr.params["recipient_fold"])(self.mesh)
        self.driver, self.survivors, second = build_driver(cell, self.mesh)
        self.warmup_subsets = [second]
        self.snapshot = traffic_mod.resolve(tr.params["telemetry"])
        self.plan, self.modulus = self.driver.plan, int(self.driver.plan.modulus)
        self.recipient_chunk = int(tr.params["recipient_chunk"])
        self.steps_per_round = tr.steps_per_pass
        everywhere = traffic_mod.replicated(self.devices, self.mesh)
        self.fold_in = jax.jit(jax.random.fold_in, out_shardings=everywhere)
        stage("program")

        # the input and the reference's sums of it, made on the device from
        # the seed by one program, chunk by chunk, every shard on its own chip
        make = traffic_mod.chunk_maker(tr, cell.dim, self.modulus, self.devices, self.mesh)
        seed_key = jax.random.key(seed)
        input_key = self.fold_in(seed_key, 0)
        self.share_key = self.fold_in(seed_key, 1)
        half_sums = jax.device_put(jnp.zeros((2, cell.dim), jnp.int64), everywhere)
        self.chunks, columns = [], []
        for i in range(tr.steps_per_pass):
            chunk, half_sums, strided = make(
                input_key, jax.device_put(jnp.int32(i), everywhere), half_sums
            )
            self.chunks.append(chunk)
            columns.append(strided)
        half_sums, columns = np.asarray(half_sums), [np.asarray(c) for c in columns]
        stage("input_on_device")
        self.want = reference.aggregate(
            half_sums, np.concatenate(columns), self.modulus, tr.passes, tr.rows
        )
        stage("reference_on_host")
        self.chunk_bytes = int(self.chunks[0].nbytes)  # all chips together
        # a step hands on, on a chip, the accumulator and its own rows' seeds
        # and counts
        self.acc_bytes = (
            int(np.prod(self.driver.acc_shape)) * 8
            + tr.chunk // cell.chips * (_seed_words(self.driver) + 1) * 4
        )
        self.rounds_run = self.seeds_to_recipient = self.seeds_repeated = 0
        self.unmasked_reveals = self.slack_exhausted_rows = 0
        self.mask_parts_mismatched = None  # until warm-up has compared them
        self.combine_at_start = self._combine()

    def _combine(self) -> list:
        """What the program's counters of its combine read: seeds, folds, chips."""
        counters = self.snapshot(0)
        return [hostfed_fold._reading(counters, "counters", name) for name in COMBINE]

    def run_round(self, index: int, spans, subsets=None):
        """One round. Returns ``(matched, clerk_sums)``; ``subsets`` (warm-up
        only) are further clerk subsets that must reveal the same, and say
        that this is the warm-up, which also compares two mask parts."""
        driver = self.driver
        with spans("round", index):
            key = self.fold_in(self.share_key, index)
            with spans("dispatch", index):
                acc, seeds, counts = driver.fold_chunks(self.chunks, key)
            with spans("fold", index):
                acc.block_until_ready()
            with spans("fetch", index):
                acc_host = np.asarray(acc)
                counts_host = [np.asarray(c) for c in counts]
            with spans("epilogue", index):
                clerk_sums = driver.clerk_sums(acc_host)
                short = driver.short_windows(counts_host)
                masked = driver.reveal(clerk_sums, self.survivors)
            with spans("unmask", index):
                # as a recipient receives them: one vector of int64 words each
                seed_rows = np.concatenate([np.asarray(s) for s in seeds])
                uploads = list(seed_rows.astype(np.int64))
                got = driver.unmask(masked, uploads, chunk=self.recipient_chunk)
            with spans("check", index):
                matched = bool(np.array_equal(got, self.want))
                self.unmasked_reveals += bool(np.array_equal(masked, self.want))
                self.slack_exhausted_rows += short
                self.seeds_repeated += repeated_rows(seed_rows)
        for subset in subsets or ():
            # the same masks come off: the masked aggregates must agree
            matched = matched and bool(np.array_equal(driver.reveal(clerk_sums, subset), masked))
        if subsets is not None:
            self.mask_parts_mismatched = self._mask_parts_mismatched(seed_rows)
        self.rounds_run += 1
        self.seeds_to_recipient += len(uploads)
        return matched, clerk_sums

    def _mask_parts_mismatched(self, seeds) -> int:
        """Of the recipient's first and last fold of this round's seeds, put
        sharded as the program puts them, how many gave on their chip (the
        first call's first chip, the last call's last) another partial mask
        sum than the plain reference over that chip's seeds."""
        import jax

        dim, modulus = self.cell.dim, self.modulus
        chips, own = self.cell.chips, self.recipient_chunk
        backend = masked_fold._fold_backend(self.devices)
        by_rows = traffic_mod.chunk_sharding(self.devices, self.mesh)
        calls = [(seeds[: own * chips], 0)]
        if len(seeds) > own * chips:
            calls.append((seeds[-own * chips :], chips - 1))
        mismatched = 0
        for batch, chip in calls:
            _met, parts, _counts = self.recipient_fold(
                jax.device_put(batch, by_rows), dim, modulus, backend
            )
            want = reference_chacha.mask_sum(batch[chip * own : (chip + 1) * own], dim, modulus)
            mismatched += not np.array_equal(np.asarray(parts)[chip], want)
        return mismatched

    def compared(self) -> dict:
        parts = self.mask_parts_mismatched
        rows, chips = self.cell.traffic.rows, self.cell.chips
        seeds, folds, fold_chips = (
            now - start for now, start in zip(self._combine(), self.combine_at_start)
        )
        seeds = min(seeds, self.seeds_to_recipient)
        folds = max(folds, self.rounds_run * (rows // (self.recipient_chunk * chips)))
        return {
            "unmasked_reveals": {"value": self.unmasked_reveals, "limit": 0},
            "slack_exhausted_rows": {"value": self.slack_exhausted_rows, "limit": 0},
            # a warm-up that never compared them has not shown them equal
            "mask_parts_mismatched": {"value": 2 if parts is None else parts, "limit": 0},
            "seeds_repeated": {"value": self.seeds_repeated, "limit": 0},
            "seeds_short": {
                "value": max(0, self.rounds_run * rows - seeds), "limit": 0,
            },
            "unmask_chips_short": {"value": max(0, folds * chips - fold_chips), "limit": 0},
        }

    def memory_peak_bytes(self) -> int:
        """The peak on the fullest of the cell's chips (0 where the backend
        reports none, as the CPU does)."""
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in self.devices]
        return int(max(peaks))


def _described(cell, devices):
    """The driver's sharded masked step, the recipient's sharded fold, the
    input's maker and the shapes they take, placed on ``devices`` (attached or
    only described). Holds no array."""
    import jax

    tr = cell.traffic
    devices = list(devices[: cell.chips])
    mesh = traffic_mod.make_mesh(tr, devices)
    driver, _survivors, _second = build_driver(cell, mesh)
    small = traffic_mod.replicated(devices, mesh)
    by_rows = traffic_mod.chunk_sharding(devices, mesh)

    def placed(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=small)

    key = jax.eval_shape(lambda: jax.random.key(0))
    key, index = placed(key.shape, key.dtype), placed((), "int32")
    chunk = jax.ShapeDtypeStruct((tr.chunk, cell.dim), driver.input_dtype, sharding=by_rows)
    seeds = jax.ShapeDtypeStruct(
        (int(tr.params["recipient_chunk"]) * cell.chips, _seed_words(driver)), "uint32",
        sharding=by_rows,
    )
    fold = traffic_mod.resolve(tr.params["recipient_fold"])(mesh)
    maker = traffic_mod.chunk_maker(tr, cell.dim, int(driver.plan.modulus), devices, mesh)
    return {
        "step": (driver.step, (placed(driver.acc_shape, "int64"), chunk, key, index)),
        "fold": (
            fold, (seeds, cell.dim, int(driver.plan.modulus), masked_fold._fold_backend(devices))
        ),
        "input": (maker, (key, index, placed((2, cell.dim), "int64"))),
    }


def steps(cell, devices) -> list:
    """``[(jitted, example arguments)]``: the driver's sharded masked chunk
    step at a ``(chunk, dim)`` chunk sharded by rows, then the recipient's
    sharded fold at the shape the program's combine runs it."""
    described = _described(cell, devices)
    return [described["step"], described["fold"]]


def input_maker(cell, devices) -> tuple:
    """``(jitted, example arguments)`` of the program that makes one chunk of
    the resident input in set-up, for the compile rehearsal."""
    return _described(cell, devices)["input"]
