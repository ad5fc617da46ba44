"""Faults of the masked round over a mesh, handed to the harness through a
traffic file's dotted names (as ``faulty_hostfed_masked.py``'s are): drivers
that break one of the configuration's three guarantees of its own. The
benchmark must call a run with any of them incorrect, each by the comparison
that is there for it."""

from __future__ import annotations

import dataclasses


def _real(scheme, dim, entry, chunk, masking, mesh):
    from sda_tpu.parallel import fold_round

    return fold_round(scheme, dim, entry, chunk, masking=masking, mesh=mesh)


def _with_unmask(unmask, real):
    """The program's driver, but for its ``unmask``."""
    from sda_tpu.parallel import FoldRound

    faulty = type("FaultyRound", (FoldRound,), {"unmask": unmask})
    return faulty(**{f.name: getattr(real, f.name) for f in dataclasses.fields(real)})


def _step_on_one_key(real):
    """The driver's sharded masked step with the chip's mesh position left out
    of the key: every chip draws the same seeds (and the same share
    randomness) for its rows. The masks are real and the recipient takes them
    off, so the aggregate is right: only the seeds tell."""
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from sda_tpu.parallel.masked import masked_chunk

    chunk_fn = masked_chunk(real.entry, real.plan, real.masking)

    def local_step(secrets, key):
        acc, seeds, counts = chunk_fn(secrets, key)
        return lax.psum(acc, axis_name="p"), seeds, counts

    mapped = jax.shard_map(
        local_step, mesh=real.mesh, in_specs=(P("p", "d"), P()),
        out_specs=(P(None, "d", None), P("p", None), P("p")), check_vma=False,
    )

    def masked_step(acc, chunk, key, i):
        out, seeds, counts = mapped(chunk, jax.random.fold_in(key, i))
        return acc + out, seeds, counts

    return jax.jit(masked_step)


def _dropping_a_seed(self, masked_aggregate, seeds, *, chunk=None):
    """The last participant's seed never reaches the combine: its mask stays
    in the aggregate."""
    from sda_tpu.parallel import FoldRound

    return FoldRound.unmask(self, masked_aggregate, list(seeds)[:-1], chunk=chunk)


def _on_one_chip(self, masked_aggregate, seeds, *, chunk=None):
    """The recipient's combine as a round without a mesh runs it: every seed
    re-expanded on the first chip, the others idle. The aggregate is right."""
    from sda_tpu.parallel import FoldRound

    return FoldRound.unmask(
        dataclasses.replace(self, mesh=None), masked_aggregate, seeds, chunk=chunk
    )


def same_seeds_driver(scheme, dim, entry, chunk, masking=None, mesh=None):
    real = _real(scheme, dim, entry, chunk, masking, mesh)
    return dataclasses.replace(real, step=_step_on_one_key(real))


def seed_dropping_driver(scheme, dim, entry, chunk, masking=None, mesh=None):
    return _with_unmask(_dropping_a_seed, _real(scheme, dim, entry, chunk, masking, mesh))


def one_chip_combine_driver(scheme, dim, entry, chunk, masking=None, mesh=None):
    return _with_unmask(_on_one_chip, _real(scheme, dim, entry, chunk, masking, mesh))
