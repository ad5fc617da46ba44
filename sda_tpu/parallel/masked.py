"""The masked fabric entry: a mask stage in front of a chunk entry.

Under the upstream's ``LinearMaskingScheme::ChaCha`` (protocol/schemes.py
``ChaChaMasking``; reference: client/src/crypto/masking/chacha.rs:29-52)
every participant draws a seed, adds the seed's ChaCha20 expansion to its
vector mod p *before* sharing, and uploads only the seed to the recipient.
The clerks then sum shares of masked values, and the recipient re-expands
every seed, sums the masks and takes them off what the clerks reveal
(crypto/masking.py ``ChaChaMasker.combine`` / ``.unmask``).

:func:`masked_chunk` puts that stage in front of any chunk entry
``entry(secrets, key, plan) -> accumulator`` (``sumfirst.value_limb_sums_chunk``)
and returns ``fn(secrets, key) -> (accumulator, seeds, counts)``:

* ``fabric.mask/seed`` — one fresh seed a row from the step's key, a stream
  of its own beside the share randomness' (the key is split);
* ``fabric.mask/expand`` — ``ops.chacha_pallas.expand_seeds_counts``, the very
  function the recipient's fold runs, so both sides expand to the same bits;
  the rounds run in the Pallas kernel where the program is compiled for a TPU
  and in the jnp twin elsewhere (``backend="auto"``);
* ``fabric.mask/add`` — ``x + mask``, less p where that reaches p: both are
  canonical and p < 2^62, so the sum needs no remainder;
* the masked chunk through ``entry`` unchanged, so its own scopes
  (``fabric.input``, ``fabric.rand``) keep their meaning.

The seeds and the accepted-draw counts leave the step beside the accumulator:
state that does not accumulate by addition. The seeds are the recipient's
third input; the counts are what :func:`count_short_windows` checks on the
host, in the round's epilogue, because a jitted step cannot read them (a row
whose keystream window held fewer than ``dim`` accepted draws has an
undefined mask tail: about 1e-9 a row).

Over a mesh the stage is the same function inside the round driver's
``shard_map`` body, on a chip's own rows: the key it is handed there has the
chip's mesh position folded in (``engine.fold_mesh_axes``, by the driver's
step), so a chip's seeds are its own. All chips masking under the same seeds
is what no aggregate can show: equal masks cancel as well as distinct ones.
Both kernels lower inside that body as they do under plain ``jit`` (there a
kernel needs no partitioning rule: it sees the chip's local seeds).

Nobody has to wrap this by hand: the round driver takes the masking scheme as
an argument of the round (``round.fold_round(..., masking=)``, and the mesh
as ``mesh=``), puts this
stage in front of the paired entry in its jitted ``masked_step``, keeps every
step's seeds and counts through ``fold_chunks`` and ``fold_host_rows``, and
carries the slack check and the unmasking (``FoldRound.short_windows``,
``.unmask``).
"""

from __future__ import annotations

import numpy as np

from .. import telemetry
from ..ops.jaxcfg import ensure_x64
from ..protocol import ChaChaMasking
from .engine import AggregationPlan

#: a seed is whole uint32 words, and ChaCha's key holds eight of them
MAX_SEED_BITS = 256

#: the add needs no remainder while 2p fits a signed 64-bit lane
MAX_MASKED_MODULUS = 1 << 62


def masked_chunk(entry, plan: AggregationPlan, masking: ChaChaMasking, *, backend: str = "auto"):
    """``fn(secrets (C, dim), key) -> (entry's accumulator, seeds (C, w)
    uint32, counts (C,) int32)``: every row of the chunk masked under a fresh
    seed, then handed to ``entry`` (module doc). Traceable; the caller jits.
    ``backend`` as in ``ops.chacha_pallas.expand_seeds_counts``."""
    if not isinstance(masking, ChaChaMasking):
        raise TypeError(f"the masked fabric entry masks under ChaCha, not {masking!r}")
    p, dim = plan.modulus, plan.dim
    if masking.modulus != p or masking.dimension != dim:
        raise ValueError("the masking scheme's modulus and dimension are not the plan's")
    if not 0 < masking.seed_bitsize <= MAX_SEED_BITS:
        raise ValueError(f"seed_bitsize must be in (0, {MAX_SEED_BITS}]")
    if p >= MAX_MASKED_MODULUS:
        raise ValueError("x + mask must fit int64 unreduced: p < 2^62")
    words = (masking.seed_bitsize + 31) // 32  # as ``ChaChaMasker`` counts them

    def fn(secrets, key):
        ensure_x64()
        import jax
        import jax.numpy as jnp

        from ..ops.chacha_pallas import expand_seeds_counts

        share_key, mask_key = jax.random.split(key)
        with jax.named_scope("fabric.mask/seed"):
            seeds = jax.random.bits(mask_key, (secrets.shape[0], words), dtype=jnp.uint32)
        with jax.named_scope("fabric.mask/expand"):
            masks, counts = expand_seeds_counts(seeds, dim, p, backend)
        with jax.named_scope("fabric.mask/add"):
            total = secrets.astype(jnp.int64) + masks
            masked = jnp.where(total >= p, total - p, total).astype(secrets.dtype)
        return entry(masked, share_key, plan), seeds, counts

    return fn


def count_short_windows(counts, dim: int) -> int:
    """The slack check of the step's side, for whoever fetched a chunk step's
    ``counts``: how many of these rows' keystream windows held fewer than
    ``dim`` accepted draws (their masks are not their seeds' expansions, so
    the round they belong to must not be revealed). Counts the rows that were
    masked (``sda_fabric_masked_rows_total``) and those that came short
    (``sda_mask_slack_exhausted_total{side="participant"}``; the recipient's
    fold counts its own side and recovers on the host)."""
    from ..ops.chacha_pallas import count_slack_exhausted

    counts = np.asarray(counts)
    short = int(np.count_nonzero(counts < dim))
    telemetry.counter(
        "sda_fabric_masked_rows_total", "rows masked by the fabric's mask stage"
    ).inc(int(counts.size))
    count_slack_exhausted("participant", short)
    return short
