"""A new kind of round as one file, as a later PR would bring it: a masked
round in miniature. Every chunk step masks its chunk's first row with a draw
from the round's key and hands the mask on beside the accumulator (state that
does not accumulate by addition, which only the step can make: only it sees
the key); after reconstruct a stage of its own, under a span of its own,
takes the masks off what the clerks revealed. It knows a ``scheme.kind`` of
its own, ``toy_masked_packed_shamir``.

The test drops this file into a temporary copy of the benchmark under
another name; nothing that is there is edited.
"""

import dataclasses

import numpy as np

from benchmark.rounds import packed_fold

KIND = "toy_masked_packed_shamir"
span_names = ("dispatch", "fold", "fetch", "epilogue", "unmask", "check")


def _unmasked(cell):
    """The cell as the unmasked round reads it: this round's scheme is
    packed Shamir underneath."""
    if cell.config["scheme"]["kind"] != KIND:
        raise packed_fold.HarnessError(f"unknown scheme kind {cell.config['scheme']['kind']!r}")
    scheme = dict(cell.config["scheme"], kind="packed_shamir")
    return dataclasses.replace(cell, config=dict(cell.config, scheme=scheme))


def _masked(step):
    import jax
    import jax.numpy as jnp

    def masked_step(acc, chunk, key, i):
        draw = jax.random.randint(
            jax.random.fold_in(key, i + 1_000_000), chunk.shape[1:], 0, 1000
        ).astype(chunk.dtype)
        mask = jnp.where(chunk[0] >= draw, draw, 0)  # the masked value stays canonical
        return step(acc, chunk.at[0].add(-mask), key, i), mask

    return jax.jit(masked_step)


def steps(cell, devices):
    ((step, args),) = packed_fold.steps(_unmasked(cell), devices)
    return [(_masked(step), args)]


class Session(packed_fold.Session):
    def __init__(self, cell, seed, devices, stages=None):
        super().__init__(_unmasked(cell), seed, devices, stages)
        self.masked_step = _masked(self.program.step)
        self.warmup_subsets = []

    def run_round(self, index, spans, subsets=None):
        with spans("round", index):
            key = self.fold_in(self.share_key, index)
            with spans("dispatch", index):
                acc, masks = self.zero_acc, []
                for i, step_number in enumerate(self.step_index):
                    chunk = self.chunks[i % len(self.chunks)]
                    acc, mask = self.masked_step(acc, chunk, key, step_number)
                    masks.append(mask)  # handed on: the recipient's third input
            with spans("fold", index):
                acc.block_until_ready()
            with spans("fetch", index):
                acc_host = np.asarray(acc)
            with spans("epilogue", index):
                clerk_sums = np.asarray(self.program.epilogue(acc_host))
                masked = self._reveal(clerk_sums, self.program.survivors)
            with spans("unmask", index):
                total = sum(np.asarray(m).astype(object) for m in masks)
                self.masks_total = int(total.sum())
                got = ((masked.astype(object) + total) % self.modulus).astype(np.int64)
            with spans("check", index):
                matched = bool(np.array_equal(got, self.want))
                self.masked_differs = not np.array_equal(masked, self.want)
        return matched, clerk_sums
