"""Pallas TPU kernel for ChaCha20 keystream expansion.

The ChaCha masking scheme (crypto/masking.py; reference:
client/src/crypto/masking/chacha.rs) makes the *recipient* re-expand every
participant's seed to a dim-length mask at reveal time — for 1M
participants x 100K dims that is ~3e9 ChaCha blocks, the single biggest
VPU-bound workload in the system (reference hot loop:
client/src/receive.rs:102-118 + chacha.rs:56-77). The jnp twin
(ops/chacha.py) is correct but materializes 16 full word tensors between
every one of the 80 quarter rounds, bouncing through HBM; this kernel keeps
the whole 16-word state in VMEM/registers for all 20 rounds and touches HBM
exactly twice per block (load initial state, store keystream).

Layout: states are carried as ``(16, n_blocks)`` uint32 — one word per
sublane row, blocks along the 128-wide lane axis — so every quarter-round
op is a full-width VPU op on ``(tile,)`` lanes. The grid tiles the block
axis; each kernel instance processes ``tile`` blocks independently (ChaCha
blocks share no state). Multi-seed batches flatten (seeds x blocks) onto
the same lane axis — one kernel launch expands every participant's stream.

Bit parity: every path (numpy host, jnp, Pallas) runs the same djb quarter
round over states from the one state builder (``chacha_state_jnp``), so
outputs are bit-identical — asserted in tests/test_ops_field.py on the
interpreter and by ``chip_smoke.py`` on the TPU. ``ChaChaMasker.combine``
(crypto/masking.py) dispatches here for large reveal batches.
"""

from __future__ import annotations

import logging

from .. import telemetry
from .chacha import apply_rounds_jnp, chacha_rounds_jnp, chacha_state_jnp, rand03_zone

# lane-axis tile: 512 blocks x 16 words x 4 B x 2 (in+out) = 64 KiB of VMEM
_TILE = 512


def _rounds_kernel(state_ref, out_ref):
    init = [state_ref[i, :] for i in range(16)]
    # fully unrolled inside the kernel; round body shared with the jnp twin
    x = apply_rounds_jnp(list(init))
    for i in range(16):
        out_ref[i, :] = x[i] + init[i]


def _rounds_pallas(states, *, interpret: bool = False):
    """(N, 16) uint32 initial states -> (N, 16) keystream via the kernel."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from .jaxcfg import I32_ZERO as zero  # literal 0 would trace as i64

    n = states.shape[0]
    padded = max(-(-n // _TILE), 1) * _TILE
    st = jnp.zeros((16, padded), dtype=jnp.uint32).at[:, :n].set(states.T)
    out = pl.pallas_call(
        _rounds_kernel,
        grid=(padded // _TILE,),
        in_specs=[pl.BlockSpec((16, _TILE), lambda i: (zero, i))],
        out_specs=pl.BlockSpec((16, _TILE), lambda i: (zero, i)),
        out_shape=jax.ShapeDtypeStruct((16, padded), jnp.uint32),
        interpret=interpret,
        name="chacha_rounds",
    )(st)
    return out[:, :n].T


def chacha_blocks_pallas(
    key_words, first_counter: int, n_blocks: int, *, interpret: bool = False
):
    """Pallas twin of ``chacha_blocks``: (n_blocks, 16) uint32 keystream."""
    state = chacha_state_jnp(key_words, first_counter, n_blocks)
    return _rounds_pallas(state, interpret=interpret)


def default_backend() -> str:
    """Which rounds implementation a caller that must *name* one ahead of
    the compile takes on this process's JAX backend (``combine_masks_device``:
    the name is a static argument of its jitted fold and the label of its
    counter): the compiled kernel on a TPU, the jnp twin anywhere else
    (there Pallas only offers its interpreter, slower than jnp). A kernel
    that fails to compile on the TPU raises at its call site."""
    import jax

    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def _rounds(states, backend: str):
    """Dispatch ``(N, 16) -> (N, 16)`` rounds by backend name.

    ``auto`` is decided where the program is lowered, from the devices it is
    compiled for (``lax.platform_dependent``): the compiled kernel for a TPU,
    attached or only described, the jnp twin for anything else. ``pallas`` /
    ``interpret`` / ``jnp`` force a specific path (interpret = Pallas
    interpreter, for CPU tests of the kernel source).
    """
    if backend == "auto":
        from jax import lax

        return lax.platform_dependent(states, tpu=_rounds_pallas, default=chacha_rounds_jnp)
    if backend == "pallas":
        return _rounds_pallas(states)
    if backend == "interpret":
        return _rounds_pallas(states, interpret=True)
    if backend == "jnp":
        return chacha_rounds_jnp(states)
    raise ValueError(f"unknown backend {backend!r}")


class SlackExhausted(RuntimeError):
    """A seed's keystream window held fewer than ``dim`` accepted draws.

    ~1e-9 per *row* (6-sigma margin), so order 1e-3 per 1M-row reveal;
    ``combine_masks_device`` recovers by host-expanding only the affected
    chunk — the penalty is bounded, never a full host re-run."""


def _window_pairs(dim: int, modulus: int) -> int:
    """How many u64 pairs to generate so every row holds >= dim accepted
    draws with ~6-sigma margin.

    The accepted sequence is a deterministic prefix-filter of the keystream
    (first ``dim`` pairs below the rejection zone, in stream order), so
    overgeneration never changes results — the host path (expand_seed)
    produces the identical sequence by extending the stream on demand.
    Rejection probability ``q = (u64::MAX % m + 1) / 2^64`` (the rand-0.3
    zone; never zero — power-of-two moduli reject too) reaches 1/2 at the
    maximum m = 2^63, so the window must scale with q, not use a fixed
    slack."""
    # rand-0.3 zone semantics: 2^64 - zone values rejected out of 2^64,
    # derived from the one shared zone definition (ops/chacha.py)
    q = ((1 << 64) - rand03_zone(modulus)) / float(1 << 64)
    import math

    expected = dim / (1.0 - q)
    margin = 6.0 * math.sqrt(expected * q) / (1.0 - q)
    return dim + int(expected - dim + margin) + 8


def _first_accepted(hi, lo, ok, dim: int):
    """Stable compaction: ``(P, window)`` uint32 word pairs and which of them
    are accepted -> ``(P, dim)`` pairs, the first ``dim`` accepted of each
    row in stream order; slots past a row's last accepted draw are 0.

    An accepted draw moves left by the number of rejected draws before it,
    at most ``window - dim`` in a row that holds ``dim`` accepted ones. It
    moves there bit by bit of that number, lowest first, each stage one
    shift of the whole row by a power of two and a select: no two draws
    ever claim one slot (two accepted draws lie further apart than their
    shifts differ), and the order holds. Why not simpler: a scatter by the
    prefix sum writes one element at a time on a TPU (6.9 s of a 7.0 s fold
    of 500 seeds at dim 100 000), and a stable sort of rows this wide takes
    its compiler 37 s (PERF.md section 6, PR 32). Draws that would move
    further (only in a row that comes short, whose mask the caller drops by
    its count) are let go."""
    import jax.numpy as jnp

    rows, window = ok.shape
    max_shift = window - dim
    rejected = (~ok).astype(jnp.int32)
    before = jnp.cumsum(rejected, axis=1) - rejected
    # the shift each slot's draw still has to make, as a whole; -1: no draw
    shift = jnp.where(ok & (before <= max_shift), before, -1)

    def from_right(x, step, fill):
        pad = jnp.full((rows, step), fill, x.dtype)
        return jnp.concatenate([x[:, step:], pad], axis=1)

    for bit in range(max_shift.bit_length()):
        step = 1 << bit
        coming = from_right(shift, step, -1)
        arrives = (coming >= 0) & ((coming >> bit) & 1 == 1)
        stays = (shift >= 0) & ((shift >> bit) & 1 == 0)
        hi = jnp.where(arrives, from_right(hi, step, 0), hi)
        lo = jnp.where(arrives, from_right(lo, step, 0), lo)
        shift = jnp.where(arrives, coming, jnp.where(stays, shift, -1))
    held = shift[:, :dim] >= 0
    return jnp.where(held, hi[:, :dim], 0), jnp.where(held, lo[:, :dim], 0)


def expand_seeds_counts(seed_words, dim: int, modulus: int, backend: str = "jnp"):
    """Jit-safe core of :func:`expand_seeds_batch`: ``(P, w<=8)`` uint32
    seeds -> ``((P, dim) int64 masks, (P,) int32 accepted-draw counts)``.

    Pure device computation, traceable under ``jax.jit`` / inside larger
    fabrics: the slack guard is NOT applied here — a row whose window held
    fewer than ``dim`` accepted draws has ``counts[p] < dim`` and undefined
    trailing mask values; callers MUST check ``counts`` (host-side, in
    their epilogue) before using the masks. :func:`expand_seeds_batch` is
    the eager wrapper that does exactly that and raises ``SlackExhausted``.
    ``backend`` as in ``_rounds``.
    """
    from .jaxcfg import ensure_x64

    ensure_x64()
    import jax
    import jax.numpy as jnp

    seed_words = jnp.asarray(seed_words, dtype=jnp.uint32)
    P = seed_words.shape[0]
    if P == 0:
        return jnp.zeros((0, dim), dtype=jnp.int64), jnp.zeros((0,), dtype=jnp.int32)
    zone = rand03_zone(modulus)  # rand-0.3 exact: rejection always applies
    need_pairs = _window_pairs(dim, modulus)
    n_blocks = (need_pairs * 2 + 15) // 16
    states = jax.vmap(lambda s: chacha_state_jnp(s, 0, n_blocks))(seed_words)
    words = _rounds(states.reshape(P * n_blocks, 16), backend)
    words = words.reshape(P, n_blocks * 16)
    # a draw is (high word, low word); the two stay apart, in the chip's own
    # 32-bit lanes, until the first ``dim`` accepted ones are picked
    hi, lo = words[:, 0::2], words[:, 1::2]
    zone_hi, zone_lo = jnp.uint32(zone >> 32), jnp.uint32(zone & 0xFFFFFFFF)
    ok = (hi < zone_hi) | ((hi == zone_hi) & (lo < zone_lo))
    counts = jnp.sum(ok, axis=1).astype(jnp.int32)
    hi, lo = _first_accepted(hi, lo, ok, dim)
    compact = (hi.astype(jnp.uint64) << jnp.uint64(32)) | lo.astype(jnp.uint64)
    masks = (compact % jnp.uint64(modulus)).astype(jnp.int64)
    return masks, counts


def expand_seeds_batch(seed_words, dim: int, modulus: int, *, backend: str = "auto"):
    """(P, w<=8) uint32 seeds -> (P, dim) int64 masks, all on device at once.

    Batched twin of ``ops.chacha.expand_seed``: identical zone rejection and
    per-seed draw order (stable compaction along the pair axis) over a
    q-scaled overgenerated window (``_window_pairs``) — bit-equal to the
    host path row by row. If a row still holds fewer than ``dim`` accepted
    draws (~1e-9 per batch), raises ``SlackExhausted`` rather than return
    wrong bits. This wrapper reads the count scalar eagerly; fabrics that
    need the expansion *inside* ``jax.jit`` use :func:`expand_seeds_counts`
    and validate the returned counts in their epilogue. One flat kernel
    launch covers all P keystreams. ``backend`` as in ``_rounds``;
    ``ops.chacha.expand_seed_jnp`` is this with P=1.
    """
    masks, counts = expand_seeds_counts(seed_words, dim, modulus, backend)
    import jax.numpy as jnp

    if counts.shape[0] and int(jnp.min(counts)) < dim:
        raise SlackExhausted(
            f"seed window held < {dim} accepted draws in at least one row"
        )
    return masks


def _fold_chunk(batch, dim: int, modulus: int, backend: str):
    """One reveal fold: expand + reduce fused on device; only the tiny
    (dim,) partial and (P,) accepted counts come back to host."""
    import jax
    import jax.numpy as jnp

    from .modular import mod_sum_wide_jnp

    with jax.named_scope("fabric.unmask/expand"):
        masks, counts = expand_seeds_counts(batch, dim, modulus, backend)
    with jax.named_scope("fabric.unmask/sum"):
        if modulus <= (1 << 31):
            part = jnp.sum(masks, axis=0) % jnp.int64(modulus)
        else:
            part = mod_sum_wide_jnp(masks, modulus, axis=0)
    return part, counts


#: module-level jit wrapper so the compile caches across reveal calls
#: (keyed on chunk shape + the static (dim, modulus, backend) triple);
#: built lazily because jax.jit at import time would initialize jax
_FOLD_CHUNK_JIT = None


def fold_chunk_jit():
    """The recipient's jitted fold, ``fn(seeds (P, w) uint32, dim, modulus,
    backend) -> ((dim,) partial mask sum mod m, (P,) accepted counts)``, the
    last three static: the one program ``combine_masks_device`` runs, fold
    after fold. Public so that whoever times or rehearses a reveal lowers the
    very program it runs (``benchmark/rounds/masked_fold.py``'s ``steps``)."""
    global _FOLD_CHUNK_JIT
    if _FOLD_CHUNK_JIT is None:
        import jax

        _FOLD_CHUNK_JIT = jax.jit(_fold_chunk, static_argnums=(1, 2, 3))
    return _FOLD_CHUNK_JIT


def count_slack_exhausted(side: str, rows: int) -> None:
    """``rows`` more seeds whose window held fewer than ``dim`` accepted
    draws, on the ``participant``'s side (a chunk step's counts, checked by
    ``parallel.masked.count_short_windows``) or the ``recipient``'s."""
    telemetry.counter(
        "sda_mask_slack_exhausted_total",
        "rows whose ChaCha rejection window held fewer than dim accepted draws",
        side=side,
    ).inc(rows)


#: transient device-memory budget per fold of combine_masks_device; the
#: expansion materializes ~5 chunk x dim x 8 B tensors at peak (the word
#: pairs and their shifts before and after a stage, the final masks)
_COMBINE_BYTES_BUDGET = 2 << 30


def combine_masks_device(
    seed_words, dim: int, modulus: int, *, chunk: int | None = None, backend: str = "auto"
):
    """Recipient reveal hot loop on device: Σ_p expand(seed_p) mod m.

    (P, w) uint32 seeds -> (dim,) int64 combined mask — the ChaCha
    ``SecretUnmasker``'s inner sum (reference chacha.rs:56-77) as a device
    computation, folding ``chunk`` seeds at a time. The default chunk is
    sized so one fold's ~5 transient chunk x dim x 8 B tensors fit in
    ``_COMBINE_BYTES_BUDGET`` (e.g. dim=100K -> chunk ~ 1K folds of ~2 GB),
    so the headline 1M x 100K reveal streams instead of OOMing.
    ``backend`` as in ``_rounds``.
    """
    from .jaxcfg import ensure_x64

    ensure_x64()
    import jax.numpy as jnp
    import numpy as np

    from .modular import mod_sum_wide_jnp

    if chunk is None:
        chunk = max(16, _COMBINE_BYTES_BUDGET // (5 * 8 * dim))
    if backend == "auto":  # resolved here: it is a static jit argument
        backend = default_backend()
    seed_words = np.asarray(seed_words, dtype=np.uint32)
    # same series the host paths count into (native/__init__.py), so a
    # scrape shows which implementation expanded a reveal's seeds
    telemetry.counter(
        "sda_crypto_chacha_expands_total",
        "ChaCha mask seeds expanded/combined by path",
        path=backend,
    ).inc(int(seed_words.shape[0]))

    fold = fold_chunk_jit()

    def host_fold(batch):
        # ~1e-9-per-row event: host-expand just this chunk (the host path
        # extends the stream on demand) and keep the device fold going
        from .chacha import expand_seed

        masks = jnp.asarray(np.stack([expand_seed(s, dim, modulus) for s in batch]))
        if modulus <= (1 << 31):
            return jnp.sum(masks, axis=0) % jnp.int64(modulus)
        return mod_sum_wide_jnp(masks, modulus, axis=0)

    total = jnp.zeros((dim,), dtype=jnp.int64)
    for start in range(0, seed_words.shape[0], chunk):
        batch = seed_words[start : start + chunk]
        part, counts = fold(jnp.asarray(batch), dim, modulus, backend)
        if counts.shape[0] and int(jnp.min(counts)) < dim:
            count_slack_exhausted("recipient", int(jnp.sum(counts < dim)))
            logging.getLogger(__name__).info(
                "rejection slack exhausted in chunk at %d; host-expanding it", start
            )
            part = host_fold(batch)
        total = (total + part) % jnp.int64(modulus)
    return total
