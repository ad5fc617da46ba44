"""The masked host-fed round: packed Shamir under the upstream's ChaCha
masking, a cohort that sits in host memory and crosses the host link in every
round, the recipient's re-expansion on the same chip.

Nothing of a round's input is on the chip when the round starts. The clock
runs from key to comparison: fresh round key -> ``dispatch``: the program's
feed (``FoldRound.fold_host_rows`` of a round built with a masking scheme)
over the host cohort's blocks, which puts each block on the device chunk by
chunk, a chunk's *masked* step dispatched behind it (every row a fresh seed,
the seed's ChaCha expansion added mod p, the masked chunk through the engine
the traffic file binds), with a bounded number of blocks alive and of bytes
crossing; the feed hands back the accumulator and every step's seeds and
accepted-draw counts, still on the device -> ``fold``: ``block_until_ready``
on the accumulator, the wait for the link and for the last step -> ``fetch``:
the accumulator and the counts -> ``epilogue``: the driver's host epilogue to
clerk sums, its slack check over the counts and its reveal from exactly
``reconstruction_threshold`` clerks: the *masked* aggregate -> ``unmask``: the
seeds fetched as a recipient receives them (one vector of int64 words a
participant) and the driver's ``unmask`` (``ChaChaMasker.combine``, the device
fold of the re-expanded masks, ``recipient_chunk`` seeds a fold, and
``.unmask``) -> ``check``: the whole aggregate compared with the plain
reference, bit for bit (:mod:`benchmark.reference`: the masks cancel, so the
aggregate's reference is the plain column sum of the host cohort).

**The round binds the program's driver**, as :mod:`benchmark.rounds.hostfed_fold`
does and by the same dotted paths, plus the masking scheme's class
(``masking_scheme``) and the handle on the recipient's jitted fold
(``recipient_fold``: for ``steps`` and for the warm-up's comparison of two mask
parts). The driver's factory is called with the scheme as ``masking=``: who
puts the mask stage in front of the entry, keeps the steps' seeds and counts,
checks the slack and unmasks is the driver; there are no adapters here. The
cohort is made, kept on the host and changed between rounds as ``hostfed_fold``
does it. This file imports nothing of the program.

The window holds two device programs: the driver's masked chunk step and the
recipient's fold (``steps`` gives both, the chunk step first). ``compared()``,
each with limit 0: ``hostfed_fold``'s ``fed_bytes_short`` and ``in_flight_over``,
``masked_fold``'s ``unmasked_reveals``, ``slack_exhausted_rows`` and
``mask_parts_mismatched``, and

``seeds_short``
    the rounds run times the cohort's rows, less the seeds that were handed
    on in them (at least 0): the lesser of what the program's counter
    ``sda_fabric_fed_seeds_total`` says the feed's steps handed on and of what
    this round handed to the recipient's combine. The recipient re-expands
    one seed for every row the feed put, in every round.
"""

from __future__ import annotations

import numpy as np

from benchmark import traffic as traffic_mod
from benchmark.rounds import hostfed_fold, masked_fold

#: the spans a round opens inside the harness's ``round``
span_names = ("dispatch", "fold", "fetch", "epilogue", "unmask", "check")

#: what the round reads of its traffic file beyond the generator's fields
TRAFFIC_KEYS = hostfed_fold.TRAFFIC_KEYS + ("masking_scheme", "recipient_fold", "recipient_chunk")

FED_SEEDS = "sda_fabric_fed_seeds_total"


def build_driver(cell):
    """``(driver, survivors, second subset)`` as ``hostfed_fold.build_driver``
    gives them, the driver built with the configuration's masking scheme."""
    tr = cell.traffic
    traffic_mod.require(tr.params, TRAFFIC_KEYS, tr.name)
    plain, survivors, second = hostfed_fold.build_driver(cell)
    masking = masked_fold._masking(cell, int(plain.plan.modulus))
    driver = traffic_mod.resolve(tr.params["driver"])(
        plain.scheme, cell.dim, plain.entry, tr.chunk, masking=masking
    )
    return driver, survivors, second


def _seed_words(driver) -> int:
    """uint32 words of one seed."""
    return (driver.masking.seed_bitsize + 31) // 32


class Session(hostfed_fold.Session):
    """The cell set up as the host-fed round sets it up (the cohort in host
    memory, its reference), with the program's masked driver."""

    # the recipient's first and last fold against the plain reference
    _mask_parts_mismatched = masked_fold.Session._mask_parts_mismatched

    def __init__(self, cell, seed: int, devices, stages=None):
        calls = cell.traffic.params
        # what the program must have is looked up before the cohort is made:
        # a checkout without the masked driver fails at once, not after set-up
        driver, _survivors, _second = build_driver(cell)
        self.recipient_fold = traffic_mod.resolve(calls["recipient_fold"])()
        super().__init__(cell, seed, devices, stages)
        self.driver = driver
        self.recipient_chunk = int(calls["recipient_chunk"])
        # a step hands on its seeds and counts beside the accumulator
        self.acc_bytes += cell.traffic.chunk * (_seed_words(driver) + 1) * 4
        self.round_rows = sum(block.shape[0] for block in self.blocks)
        self.unmasked_reveals = self.slack_exhausted_rows = self.seeds_to_recipient = 0
        self.mask_parts_mismatched = None  # until warm-up has compared them
        self.seeds_at_start = hostfed_fold._reading(self.snapshot(0), "counters", FED_SEEDS)

    def run_round(self, index: int, spans, subsets=None):
        """One round. Returns ``(matched, clerk_sums)``; ``subsets`` (warm-up
        only) are further clerk subsets that must reveal the same, and say
        that this is the warm-up, which also compares two mask parts."""
        self._refresh_rows(index)
        driver = self.driver
        with spans("round", index):
            key = self.fold_in(self.share_key, index)
            with spans("dispatch", index):
                acc, seeds, counts = driver.fold_host_rows(
                    self.blocks, key, in_flight=self.in_flight
                )
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
                uploads = list(np.concatenate([np.asarray(s) for s in seeds]).astype(np.int64))
                got = driver.unmask(masked, uploads, chunk=self.recipient_chunk)
            with spans("check", index):
                matched = bool(np.array_equal(got, self.want))
                self.unmasked_reveals += bool(np.array_equal(masked, self.want))
                self.slack_exhausted_rows += short
        for subset in subsets or ():
            # the same masks come off: the masked aggregates must agree
            matched = matched and bool(np.array_equal(driver.reveal(clerk_sums, subset), masked))
        if subsets is not None:
            self.mask_parts_mismatched = self._mask_parts_mismatched(np.stack(uploads))
        self.rounds_run += 1
        self.seeds_to_recipient += len(uploads)
        self.in_flight_most = max(
            self.in_flight_most,
            hostfed_fold._reading(self.snapshot(0), "gauges", hostfed_fold.IN_FLIGHT_MAX),
        )
        return matched, clerk_sums

    def compared(self) -> dict:
        handed_on = (
            hostfed_fold._reading(self.snapshot(0), "counters", FED_SEEDS) - self.seeds_at_start
        )
        seeds = min(handed_on, self.seeds_to_recipient)
        return {
            **super().compared(),
            **masked_fold.Session.compared(self),
            "seeds_short": {
                "value": max(0, self.rounds_run * self.round_rows - seeds), "limit": 0,
            },
        }


def steps(cell, devices) -> list:
    """``[(jitted, example arguments)]``: the driver's masked chunk step at a
    ``(chunk, dim)`` chunk (the unmasked step's arguments: a block is put as
    its chunks, no program slices it), then the recipient's fold at the shape
    ``combine_masks_device`` runs it."""
    import jax

    devices = list(devices[: cell.chips])
    driver, _survivors, _second = build_driver(cell)
    ((_plain_step, args),) = hostfed_fold.steps(cell, devices)
    seeds = jax.ShapeDtypeStruct(
        (int(cell.traffic.params["recipient_chunk"]), _seed_words(driver)), "uint32",
        sharding=traffic_mod.replicated(devices, None),
    )
    fold = traffic_mod.resolve(cell.traffic.params["recipient_fold"])()
    backend = masked_fold._fold_backend(devices)
    return [
        (driver.step, args),
        (fold, (seeds, cell.dim, int(driver.plan.modulus), backend)),
    ]


def input_maker(cell, devices) -> tuple:
    return hostfed_fold.input_maker(cell, devices)
