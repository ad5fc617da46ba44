"""A new kind of round as one file, as a later PR would bring it: a masked
round in miniature. Every chunk step masks its chunk's first row with a draw
from the round's key and hands the mask on beside the accumulator (state that
does not accumulate by addition, which only the step can make: only it sees
the key); after reconstruct a stage of its own, under a span of its own, sums
the masks on the device with a second jitted program and takes them off what
the clerks revealed. So the window holds two device programs, as the masked
round's does (the chunk step and the recipient's fold of the re-expanded
masks), and ``steps`` gives both, the chunk step first. It knows a
``scheme.kind`` of its own, ``toy_masked_packed_shamir``, and makes a
comparison of its own (``compared``): the reveal before unmasking must differ
from the plain aggregate, or the masks did nothing.

The tests drop this file into a temporary copy of the benchmark as
``benchmark/rounds/<name>.py``; nothing that is there is edited.
"""

import dataclasses

import numpy as np

from benchmark.rounds import packed_fold

KIND = "toy_masked_packed_shamir"
span_names = ("dispatch", "fold", "fetch", "epilogue", "unmask", "check")
TRAFFIC_KEYS = packed_fold.TRAFFIC_KEYS
#: a mask is drawn below this
MASK_BELOW = 1000


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
            jax.random.fold_in(key, i + 1_000_000), chunk.shape[1:], 0, MASK_BELOW
        ).astype(chunk.dtype)
        mask = jnp.where(chunk[0] >= draw, draw, 0)  # the masked value stays canonical
        return step(acc, chunk.at[0].add(-mask), key, i), mask

    return jax.jit(masked_step)


def _mask_folder():
    """The second device program of the window: one step's mask added to the
    running sum of the masks."""
    import jax
    import jax.numpy as jnp

    def unmask_fold(total, mask):
        return total + mask.astype(jnp.int64)

    return jax.jit(unmask_fold)


def steps(cell, devices):
    import jax

    ((step, args),) = packed_fold.steps(_unmasked(cell), devices)
    _acc, chunk, _key, index = args
    on_every_chip = index.sharding
    total = jax.ShapeDtypeStruct(chunk.shape[1:], "int64", sharding=on_every_chip)
    mask = jax.ShapeDtypeStruct(chunk.shape[1:], chunk.dtype, sharding=on_every_chip)
    return [(_masked(step), args), (_mask_folder(), (total, mask))]


def input_maker(cell, devices):
    return packed_fold.input_maker(_unmasked(cell), devices)


class Session(packed_fold.Session):
    def __init__(self, cell, seed, devices, stages=None):
        import jax
        import jax.numpy as jnp

        super().__init__(_unmasked(cell), seed, devices, stages)
        self.masked_step = _masked(self.program.step)
        self.unmask_fold = _mask_folder()
        self.no_masks = jax.device_put(
            jnp.zeros((cell.dim,), jnp.int64), self.step_index[0].sharding
        )
        self.warmup_subsets = []
        self.unmasked_reveals = 0

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
                total = self.no_masks
                for mask in masks:
                    total = self.unmask_fold(total, mask)
                total = np.asarray(total).astype(object)
                got = ((masked.astype(object) + total) % self.modulus).astype(np.int64)
            with spans("check", index):
                matched = bool(np.array_equal(got, self.want))
                self.unmasked_reveals += bool(np.array_equal(masked, self.want))
        return matched, clerk_sums

    def compared(self):
        """Rounds, warm-up included, in which what the clerks revealed
        equalled the plain aggregate before the masks were taken off."""
        return {"unmasked_reveals": {"value": self.unmasked_reveals, "limit": 0}}
