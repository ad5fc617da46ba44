"""The masked round: packed Shamir under the upstream's ChaCha masking, a
resident input masked and folded chunk by chunk, the recipient's re-expansion
on the same chip.

The clock runs from key to comparison: fresh round key -> the masked chunk
step over every chunk of the resident input (every row a fresh seed, the
seed's ChaCha expansion added mod p, the masked chunk through the engine the
traffic file binds, the program's default share randomness) ->
``block_until_ready`` and transfer of the accumulator and the accepted-draw
counts -> host epilogue to clerk sums, the slack check of the step's side,
reconstruct from exactly ``reconstruction_threshold`` clerks: the *masked*
aggregate -> ``unmask``: the seeds fetched as a recipient receives them (one
vector of int64 words a participant), ``ChaChaMasker.combine`` (the device
fold of the re-expanded masks, ``recipient_chunk`` seeds a fold) and
``.unmask`` -> the whole aggregate compared with the plain reference, bit for
bit (:mod:`benchmark.reference`: the masks cancel, so the aggregate's
reference is the plain column sum).

The window holds two device programs: the masked chunk step and the
recipient's fold (``steps`` gives both, the chunk step first). What only this
round knows goes into ``compared()``:

``unmasked_reveals``
    rounds, warm-up included, whose reveal *before* unmasking equalled the
    plain aggregate: the masks the clerks' sums carry are real;
``slack_exhausted_rows``
    rows of the step's side whose rejection window held fewer than ``dim``
    accepted draws (the recipient's fold checks and recovers its own side);
``mask_parts_mismatched``
    in warm-up, the partial mask sums of the recipient's first and last fold
    (the very program ``combine_masks_device`` runs, at the timed shape, on
    those two batches of the round's seeds) against
    :mod:`benchmark.reference_chacha` over the same seeds (their keystream
    made on the device in blocks, the rest in numpy). The folds between are
    held by the aggregate, which every round compares whole.

Its traffic file names, beyond what :mod:`benchmark.rounds.packed_fold`
reads, the masked entry and its adapter (``masked_engine``,
``masked_engine_call``), the masking scheme's and the masker's classes, the
slack check, the handle on the recipient's jitted fold, and how many seeds
the recipient folds at a time.
"""

from __future__ import annotations

import numpy as np

from benchmark import reference_chacha
from benchmark import traffic as traffic_mod
from benchmark.harness import HarnessError
from benchmark.rounds import packed_fold

#: the spans a round opens inside the harness's ``round``
span_names = ("dispatch", "fold", "fetch", "epilogue", "unmask", "check")

#: what the round reads of its traffic file beyond the generator's fields
TRAFFIC_KEYS = packed_fold.TRAFFIC_KEYS + (
    "masked_engine", "masked_engine_call", "masking_scheme", "masker", "slack_check",
    "recipient_fold", "recipient_chunk",
)


def _masking(cell, modulus: int):
    """The program's masking scheme, from the configuration's ``masking``
    block; its modulus is the sharing scheme's."""
    spec = cell.config.get("masking")
    if not spec or spec.get("kind") != "chacha":
        raise HarnessError(
            f"cell {cell.name!r}: the masked round needs a `masking` block of kind chacha"
        )
    if spec["dimension"] != cell.dim:
        raise HarnessError("the masking block's dimension is not the configuration's dim")
    scheme = traffic_mod.resolve(cell.traffic.params["masking_scheme"])
    return scheme(modulus=modulus, dimension=cell.dim, seed_bitsize=spec["seed_bitsize"])


def _masked_step(cell, program, masking):
    """The jitted ``masked_step(acc, chunk, key, i) -> (acc, seeds, counts)``:
    one chunk of the resident input masked and folded; the seeds and the
    counts are handed on beside the accumulator."""
    import jax

    calls = cell.traffic.params
    if calls["accumulate"] != "sum":
        raise HarnessError(f"{cell.traffic.name}: the masked round accumulates by `sum`")
    chunk_fn = traffic_mod.resolve(calls["masked_engine_call"])(
        traffic_mod.resolve(calls["masked_engine"]), traffic_mod.resolve(calls["engine"]),
        program.plan, masking,
    )

    def masked_step(acc, chunk, key, i):
        out, seeds, counts = chunk_fn(chunk, jax.random.fold_in(key, i))
        return acc + out, seeds, counts

    return jax.jit(masked_step)


def _seed_words(masking) -> int:
    """uint32 words of one seed."""
    return (masking.seed_bitsize + 31) // 32


def _fold_backend(devices) -> str:
    """What ``combine_masks_device`` names its fold's rounds on these
    devices: the kernel on a TPU, the jnp twin elsewhere."""
    return "pallas" if devices[0].platform == "tpu" else "jnp"


def _build(cell, program):
    """``(masking scheme, jitted masked step)`` over the unmasked round's
    ``program`` (scheme, plan, epilogue and reconstruct are its own)."""
    traffic_mod.require(cell.traffic.params, TRAFFIC_KEYS, cell.traffic.name)
    if cell.traffic.mesh:
        raise HarnessError(f"{cell.traffic.name}: the masked round runs on one chip")
    masking = _masking(cell, program.modulus)
    return masking, _masked_step(cell, program, masking)


class Session(packed_fold.Session):
    """The cell set up as the unmasked round sets it up (input and reference
    resident), with the masked chunk step and the recipient's masker."""

    def __init__(self, cell, seed: int, devices, stages=None):
        calls = cell.traffic.params
        traffic_mod.require(calls, TRAFFIC_KEYS, cell.traffic.name)
        # what the program must have is looked up before the input is made: a
        # checkout without the masked entry fails at once, not after set-up
        self.slack_check = traffic_mod.resolve(calls["slack_check"])
        self.recipient_fold = traffic_mod.resolve(calls["recipient_fold"])()
        make_masker = traffic_mod.resolve(calls["masker"])
        traffic_mod.resolve(calls["masked_engine"])
        super().__init__(cell, seed, devices, stages)
        masking, self.masked_step = _build(cell, self.program)
        self.masker = make_masker(masking.modulus, masking.dimension, masking.seed_bitsize)
        self.recipient_chunk = int(calls["recipient_chunk"])
        # a step hands on its seeds and counts beside the accumulator
        self.acc_bytes += cell.traffic.chunk * (_seed_words(masking) + 1) * 4
        self.unmasked_reveals = self.slack_exhausted_rows = 0
        self.mask_parts_mismatched = None  # until warm-up has compared them

    def run_round(self, index: int, spans, subsets=None):
        """One round. Returns ``(matched, clerk_sums)``; ``subsets`` (warm-up
        only) are further clerk subsets that must reveal the same, and say
        that this is the warm-up, which also compares two mask parts."""
        program, dim = self.program, self.cell.dim
        with spans("round", index):
            key = self.fold_in(self.share_key, index)
            with spans("dispatch", index):
                acc, seeds, counts = self.zero_acc, [], []
                for i, step_number in enumerate(self.step_index):
                    chunk = self.chunks[i % len(self.chunks)]  # passes wrap
                    acc, step_seeds, step_counts = self.masked_step(acc, chunk, key, step_number)
                    seeds.append(step_seeds)  # handed on: the recipient's third input
                    counts.append(step_counts)
            with spans("fold", index):
                acc.block_until_ready()
            with spans("fetch", index):
                acc_host = np.asarray(acc)
                counts_host = np.concatenate([np.asarray(c) for c in counts])
            with spans("epilogue", index):
                clerk_sums = np.asarray(program.epilogue(acc_host))
                short = self.slack_check(counts_host, dim)
                masked = self._reveal(clerk_sums, program.survivors)
            with spans("unmask", index):
                # as a recipient receives them: one vector of int64 words each
                uploads = list(np.concatenate([np.asarray(s) for s in seeds]).astype(np.int64))
                mask = self.masker.combine(uploads, chunk=self.recipient_chunk)
                got = np.mod(self.masker.unmask(mask, masked), self.modulus)
            with spans("check", index):
                matched = bool(np.array_equal(got, self.want))
                self.unmasked_reveals += bool(np.array_equal(masked, self.want))
                self.slack_exhausted_rows += short
        for subset in subsets or ():
            other = self.masker.unmask(mask, self._reveal(clerk_sums, subset))
            matched = matched and bool(np.array_equal(np.mod(other, self.modulus), self.want))
        if subsets is not None:
            self.mask_parts_mismatched = self._mask_parts_mismatched(np.stack(uploads))
        return matched, clerk_sums

    def _mask_parts_mismatched(self, uploads) -> int:
        """Of the recipient's first and last fold of this round's seeds, how
        many gave another partial mask sum than the plain reference."""
        import jax.numpy as jnp

        seeds = uploads.astype(np.uint32)
        dim, modulus, backend = self.cell.dim, self.modulus, _fold_backend(self.devices)
        first, last = seeds[: self.recipient_chunk], seeds[-self.recipient_chunk :]
        mismatched = 0
        for batch in (first, last) if len(seeds) > self.recipient_chunk else (first,):
            part, _counts = self.recipient_fold(jnp.asarray(batch), dim, modulus, backend)
            want = reference_chacha.mask_sum(batch, dim, modulus)
            mismatched += not np.array_equal(np.asarray(part), want)
        return mismatched

    def compared(self) -> dict:
        parts = self.mask_parts_mismatched
        return {
            "unmasked_reveals": {"value": self.unmasked_reveals, "limit": 0},
            "slack_exhausted_rows": {"value": self.slack_exhausted_rows, "limit": 0},
            # a warm-up that never compared them has not shown them equal
            "mask_parts_mismatched": {"value": 2 if parts is None else parts, "limit": 0},
        }


def steps(cell, devices) -> list:
    """``[(jitted, example arguments)]``: the masked chunk step, then the
    recipient's fold at the shape ``combine_masks_device`` runs it."""
    import jax

    devices = list(devices[: cell.chips])
    program = packed_fold.build_program(cell, None)
    masking, masked_step = _build(cell, program)
    ((_step, args),) = packed_fold.steps(cell, devices)
    seeds = jax.ShapeDtypeStruct(
        (int(cell.traffic.params["recipient_chunk"]), _seed_words(masking)), "uint32",
        sharding=traffic_mod.replicated(devices, None),
    )
    fold = traffic_mod.resolve(cell.traffic.params["recipient_fold"])()
    return [
        (masked_step, args),
        (fold, (seeds, cell.dim, program.modulus, _fold_backend(devices))),
    ]


def input_maker(cell, devices) -> tuple:
    return packed_fold.input_maker(cell, devices)
