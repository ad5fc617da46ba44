"""Pallas TPU kernels for ChaCha20 mask expansion: the rounds, the compaction.

The ChaCha masking scheme (crypto/masking.py; reference:
client/src/crypto/masking/chacha.rs) makes the *recipient* re-expand every
participant's seed to a dim-length mask at reveal time — for 1M
participants x 100K dims that is ~3e9 ChaCha blocks, the single biggest
VPU-bound workload in the system (reference hot loop:
client/src/receive.rs:102-118 + chacha.rs:56-77). Two stages of that
expansion bounce whole tensors through HBM when written in jax.numpy, and
each has a kernel here that keeps them on the chip:

* ``chacha_rounds``. The jnp twin (ops/chacha.py) builds a ``(P, blocks, 16)``
  tensor of initial states and materializes 16 full word tensors between
  every one of the 80 quarter rounds; the kernel takes the seeds alone, makes
  each block's state in registers (the constants, the row's key words, the
  block's index from an iota), keeps the 16 words there for all 20 rounds and
  touches HBM once a block: to store the keystream. (Built in XLA, the
  states of 500 seeds of 13 400 blocks are 4.3e8 B written, transposed and
  read a call, and cost more than the rounds: PERF.md section 6, PR 40.)
* ``chacha_compact``. Picking a row's first ``dim`` accepted draws is a
  stable compaction, a shift-and-select stage for every bit of the largest
  shift (``_first_accepted``); in XLA each stage is a pass over three
  ``(P, window)`` tensors, in the kernel a tile of eight rows stays in VMEM
  from the first stage to the last and HBM sees the draws once, going in,
  and the first ``dim`` of them once, coming out.

Layout of the rounds: a tile is eight seeds on the sublanes by a row's blocks
along the 128-wide lane axis, so each of the 16 words is whole ``(8, lanes)``
vregs and every quarter-round op a full-width VPU op; a row's key words
broadcast along the lanes. The grid tiles rows and blocks (``_rounds_plan``);
inside a grid step a loop walks the lanes, ``_ROUNDS_CHUNK`` at a time (ChaCha
blocks share no state). The kernel writes ``(16, P, blocks)``, word-major, and
nothing is padded in HBM; the transposition to stream order is XLA's. One
launch expands every participant's stream. Layout of the compaction: rows on
the sublanes, eight a tile, a row's draws along the lanes (``_compact_plan``).

Bit parity: every traced path runs the same djb quarter round
(``apply_rounds_jnp``) and the same compaction stages. The jnp twin starts
from the state builder ``chacha_state_jnp``; the kernel makes its states
itself and is held to the numpy host path's bits, which share no code with
either, in tests/test_chacha_rounds.py and tests/test_ops_field.py on the
interpreter and by ``chip_smoke.py`` on the TPU. ``ChaChaMasker.combine``
(crypto/masking.py) dispatches here for large reveal batches.

Over a device mesh both kernels run inside a ``shard_map`` body, on a chip's
local seeds (no partitioning rule is needed there): the masked round's step
(``parallel/round.py``) and the recipient's fold, which
``combine_masks_device(..., mesh=)`` spreads over the round's chips
(``fold_chunk_mesh_jit``).
"""

from __future__ import annotations

import functools
import logging

from .. import telemetry
from .chacha import apply_rounds_jnp, chacha_rounds_jnp, chacha_state_jnp, rand03_zone

#: lanes (blocks of a row) one grid step of the rounds kernel writes at most,
#: and lanes one step of its inner loop holds in registers, sixteen words of
#: ``_ROUNDS_CHUNK // 128`` vregs each. The kernel is the VPU's: 500 rows of
#: 13 400 blocks took 3.02, 2.03, 1.93 ms at 128, 256, 512 lanes a loop step
#: (4096 a grid step), 2.14, 2.03, 1.93 ms at 512, 4096, 16 384 a grid step
#: (256 a loop step) and 1.85 ms at these (a v5e; 6.47 ms from states read in
#: HBM, 1-D word rows, 13 086 grid steps; PERF.md section 6, PR 40)
_ROUNDS_LANES = 16384
_ROUNDS_CHUNK = 512


def _rounds_plan(n_blocks: int):
    """``(grid steps along a row, lanes a step)``: the row's blocks split
    evenly over the fewest steps of at most ``_ROUNDS_LANES``, a step's lanes
    rounded up to whole loop steps, so the last step's overhang past the row
    (computed, never written) stays under one loop step a grid step."""
    steps = -(-n_blocks // _ROUNDS_LANES)
    lanes = -(-n_blocks // (steps * _ROUNDS_CHUNK)) * _ROUNDS_CHUNK
    return -(-n_blocks // lanes), lanes


def _rounds_kernel(seeds_ref, out_ref, *, first_counter: int):
    """Eight rows' keystream, ``out_ref`` ``(16, 8, lanes)``: word-major, a
    row a sublane, its blocks along the lanes. The initial states are made
    here, in registers, from the rows' eight key words (``seeds_ref``, ``(8,
    8)``, broadcast along the lanes) and the blocks' indices (an iota plus
    this step's place in the row): the constants, the key, the counter's low
    word, and zeros for its high word (the entry refuses a counter that would
    reach it) and the nonce. No state is read from HBM."""
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.experimental import pallas as pl

    from .chacha import _CONSTANTS

    lanes, chunk = out_ref.shape[2], _ROUNDS_CHUNK
    # typed constants throughout: under x64 a python number traces 64 bits wide
    shape = (8, chunk)
    constants = [jnp.full(shape, c, jnp.uint32) for c in _CONSTANTS]
    key = [jnp.broadcast_to(seeds_ref[:, w : w + 1], shape) for w in range(8)]
    zeros = jnp.zeros(shape, jnp.uint32)
    lane = lax.broadcasted_iota(jnp.int32, shape, 1)
    # int32 wraps as the counter's uint32 does: the same bits
    first = jnp.int32(np.uint32(first_counter).astype(np.int32))
    here = pl.program_id(1) * jnp.int32(lanes) + first

    def step(c, carry):
        at = pl.multiple_of(c * jnp.int32(chunk), chunk)
        counter = lax.bitcast_convert_type(lane + (here + at), jnp.uint32)
        init = [*constants, *key, counter, zeros, zeros, zeros]
        # fully unrolled inside the kernel; round body shared with the jnp twin
        x = apply_rounds_jnp(list(init))
        for i in range(16):
            out_ref[i, :, pl.ds(at, chunk)] = x[i] + init[i]
        return carry

    lax.fori_loop(jnp.int32(0), jnp.int32(lanes // chunk), step, jnp.int32(0))


def _rounds_pallas(seed_words, n_blocks: int, first_counter: int = 0, *, interpret: bool = False):
    """``(P, w <= 8)`` uint32 seeds -> ``(P, n_blocks, 16)`` keystream, each
    row's blocks ``first_counter`` onwards, via the kernel ``chacha_rounds``.
    The seeds are the kernel's only operand. It writes ``(16, P, n_blocks)``
    (nothing padded in HBM: the blocks reach past the array's rows and lanes,
    and the pipeline drops what lies there); the transposition to stream
    order stays XLA's."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .jaxcfg import I32_ZERO as zero  # literal 0 would trace as i64

    rows, width = seed_words.shape
    keys = jnp.pad(seed_words.astype(jnp.uint32), ((0, 0), (0, 8 - width)))
    steps, lanes = _rounds_plan(n_blocks)
    out = pl.pallas_call(
        functools.partial(_rounds_kernel, first_counter=first_counter),
        grid=(-(-rows // 8), steps),
        in_specs=[pl.BlockSpec((8, 8), lambda i, j: (i, zero))],
        out_specs=pl.BlockSpec((16, 8, lanes), lambda i, j: (zero, i, j)),
        out_shape=jax.ShapeDtypeStruct((16, rows, n_blocks), jnp.uint32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            # the output's two buffers, and room for the loop's spills
            vmem_limit_bytes=2 * 16 * 8 * lanes * 4 + (8 << 20),
        ),
        interpret=interpret,
        name="chacha_rounds",
    )(keys)
    return jnp.transpose(out, (1, 2, 0))


def _rounds_jnp(seed_words, n_blocks: int, first_counter: int):
    """The jnp twin of :func:`_rounds_pallas`, what every platform that is not
    a TPU runs: the states from ``chacha_state_jnp``, a row at a time under
    ``vmap``, then ``chacha_rounds_jnp``. The kernel is held to its bits."""
    import jax

    states = jax.vmap(lambda s: chacha_state_jnp(s, first_counter, n_blocks))(seed_words)
    return chacha_rounds_jnp(states)


def chacha_blocks_pallas(
    key_words, first_counter: int, n_blocks: int, *, interpret: bool = False
):
    """Pallas twin of ``chacha_blocks``: (n_blocks, 16) uint32 keystream."""
    import jax.numpy as jnp

    key = jnp.asarray(key_words, dtype=jnp.uint32)[None, :]
    return _rounds(key, n_blocks, first_counter, "interpret" if interpret else "pallas")[0]


def default_backend() -> str:
    """Which rounds implementation a caller that must *name* one ahead of
    the compile takes on this process's JAX backend (``combine_masks_device``:
    the name is a static argument of its jitted fold and the label of its
    counter): the compiled kernel on a TPU, the jnp twin anywhere else
    (there Pallas only offers its interpreter, slower than jnp). A kernel
    that fails to compile on the TPU raises at its call site."""
    import jax

    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def count_blocks(path: str, blocks: int) -> None:
    """``blocks`` more ChaCha blocks whose rounds a traced program runs by
    ``path``: counted where the path is chosen, once a trace, as
    :func:`count_compaction` counts rows."""
    telemetry.counter(
        "sda_crypto_chacha_blocks_total",
        "ChaCha blocks whose rounds a traced program runs, by path "
        "(pallas | interpret | jnp)",
        path=path,
    ).inc(blocks)


def _rounds(seed_words, n_blocks: int, first_counter: int, backend: str):
    """``(P, w <= 8)`` uint32 seeds -> ``(P, n_blocks, 16)`` keystream, every
    row's blocks ``first_counter`` to ``first_counter + n_blocks``, by backend
    name.

    ``auto`` is decided where the program is lowered, from the devices it is
    compiled for (``lax.platform_dependent``; counted as this process's
    backend, which is what runs it): the compiled kernel for a TPU, attached
    or only described, the jnp twin for anything else. ``pallas`` /
    ``interpret`` / ``jnp`` force a specific path (interpret = Pallas
    interpreter, for CPU tests of the kernel source). The kernel makes the
    counter's low word only, so a counter that reaches 2^32 is refused here,
    under every name.
    """
    if backend not in ("auto", "pallas", "interpret", "jnp"):
        raise ValueError(f"unknown backend {backend!r}")
    if first_counter < 0 or first_counter + n_blocks >= 1 << 32:
        raise ValueError(
            f"blocks {first_counter} to {first_counter + n_blocks} of a row: "
            "the block counter is held to 32 bits"
        )
    count_blocks(default_backend() if backend == "auto" else backend, seed_words.shape[0] * n_blocks)
    blocks = dict(n_blocks=n_blocks, first_counter=first_counter)
    if backend == "auto":
        from jax import lax

        return lax.platform_dependent(
            seed_words,
            tpu=functools.partial(_rounds_pallas, **blocks),
            default=functools.partial(_rounds_jnp, **blocks),
        )
    if backend == "jnp":
        return _rounds_jnp(seed_words, **blocks)
    return _rounds_pallas(seed_words, **blocks, interpret=backend == "interpret")


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
    its count) are let go.

    This is the prefix sum and the stages in XLA, every stage a pass of
    three ``(P, window)`` tensors through HBM (0.0767 s for 500 rows of
    107 200 on a v5e, 0.0668 of it the thirteen stages): the path of every
    platform that is not a TPU. On a TPU the same prefix sum and stages run
    in the kernel ``chacha_compact`` (:func:`_compact_pallas`), a tile of
    eight rows held in VMEM from the first stage to the last (PERF.md
    section 6, PR 33; :func:`_compact` chooses)."""
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


#: lanes of a row one loop step handles, thirty-two vregs a tensor: a step
#: costs the same forty-odd cycles around its vregs' five each, so the stages
#: alone took 20.0, 12.3, 7.9, 5.9, 5.3, 5.9 ms at 256 to 8192 lanes and the
#: whole kernel 12.6, 8.8, 7.5 ms at 1024, 2048, 4096 (500 rows of 107 200, a
#: v5e; 5.65 ms at 4096 since a step's lanes and their right neighbours share a
#: load; PERF.md section 6, PR 33)
_COMPACT_LANES = 4096

#: what the compaction kernel may hold in VMEM: a v5e has 128 MiB of it
_COMPACT_VMEM_BUDGET = 100 << 20


def _compact_plan(window: int, dim: int):
    """How the compaction kernel lays a tile of eight rows out, from the
    shapes alone: ``(stages, lanes in, lanes worked on, scratch lanes, lanes
    out, VMEM bytes)``. The stages work on the window rounded up to whole
    loop steps; a stage looks ``step`` lanes to the right (and a step under
    a vreg one vreg more), so the input carries one vreg beyond and the
    scratch a halo of the largest step, kept at "no draw"."""
    stages = (window - dim).bit_length()
    body = -(-window // _COMPACT_LANES) * _COMPACT_LANES
    lanes_in = body + 128
    scratch = body + max(1 << max(stages - 1, 0), 128) + 128
    lanes_out = -(-dim // _COMPACT_LANES) * _COMPACT_LANES
    # inputs and outputs are double-buffered by the pipeline
    vmem = 8 * 4 * (2 * 3 * lanes_in + 3 * scratch + 2 * 2 * lanes_out)
    return stages, lanes_in, body, scratch, lanes_out, vmem


def _compact_fits(window: int, dim: int) -> bool:
    """Whether the kernel is worth lowering for this shape: a window of at
    least one lane tile with something to move, and a tile of rows that
    fits VMEM. Otherwise the compaction runs in XLA (``_first_accepted``)."""
    stages, *_rest, vmem = _compact_plan(window, dim)
    return window >= 128 and stages > 0 and vmem <= _COMPACT_VMEM_BUDGET


def _compact_kernel(
    hi_ref, lo_ref, ok_ref, out_hi_ref, out_lo_ref, hi_s, lo_s, shift_s, *, stages, window, dim, body
):
    """``_first_accepted`` on one tile of eight rows, in VMEM. First the
    shifts, a prefix sum of the rejected draws down the row, a loop step's
    lanes at a time with the count so far carried on (the blocks reach past
    the window: what lies there is anything, and reads "no draw"). Then the
    stages in place: a stage walks the row left to right in steps of
    ``_COMPACT_LANES`` lanes, reads a step's lanes and those ``step`` to their
    right, and writes the step's lanes back; it never reads what it has
    written (a draw only moves left). The first stage reads the input words,
    the last writes the outputs."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    lanes = _COMPACT_LANES
    # typed constants throughout: under x64 a python number traces 64 bits wide
    none, zero, one = jnp.int32(-1), jnp.int32(0), jnp.int32(1)
    shift_s[:, body:] = jnp.full((8, shift_s.shape[1] - body), none)
    lane = lax.broadcasted_iota(jnp.int32, (8, lanes), 1)

    def shifts(c, so_far):
        at = pl.multiple_of(c * jnp.int32(lanes), lanes)
        ok = (ok_ref[:, pl.ds(at, lanes)] != zero) & (lane < jnp.int32(window) - at)
        rejected = jnp.where(ok, zero, one)
        upto, reach = rejected, 1  # rejected draws in the ``reach`` lanes ending here
        while reach < lanes:
            left = pltpu.roll(upto, jnp.int32(reach), axis=1)
            upto = upto + jnp.where(lane >= jnp.int32(reach), left, zero)
            reach *= 2
        before = so_far + upto - rejected
        shift_s[:, pl.ds(at, lanes)] = jnp.where(
            ok & (before <= jnp.int32(window - dim)), before, none
        )
        return so_far + jnp.sum(rejected, axis=1, keepdims=True, dtype=jnp.int32)

    lax.fori_loop(zero, jnp.int32(body // lanes), shifts, jnp.zeros((8, 1), jnp.int32))
    for bit in range(stages):
        step = 1 << bit
        last = bit == stages - 1
        src = (hi_ref, lo_ref) if bit == 0 else (hi_s, lo_s)
        dst = (out_hi_ref, out_lo_ref) if last else (hi_s, lo_s)
        # a draw (sign clear) whose shift has this stage's bit set, or clear
        draw_bit = jnp.int32(step - (1 << 31))

        def chunk(c, carry):  # traced here and now, under this stage's names
            at = pl.multiple_of(c * jnp.int32(lanes), lanes)

            def here_and_right(ref):
                if step >= 128:  # whole vregs: a re-indexing
                    return ref[:, pl.ds(at, lanes)], ref[:, pl.ds(at + jnp.int32(step), lanes)]
                wide = ref[:, pl.ds(at, lanes + 128)]
                moved = pltpu.roll(wide, jnp.int32(lanes + 128 - step), axis=1)
                return wide[:, :lanes], moved[:, :lanes]

            shift, coming = here_and_right(shift_s)
            arrives = (coming & draw_bit) == jnp.int32(step)
            stays = (shift & draw_bit) == zero
            shift = jnp.where(arrives, coming, jnp.where(stays, shift, none))
            if not last:
                shift_s[:, pl.ds(at, lanes)] = shift
            for word_src, word_dst in zip(src, dst):
                word, from_right = here_and_right(word_src)
                word = jnp.where(arrives, from_right, word)
                if last:
                    word = jnp.where(shift >= zero, word, jnp.uint32(0))
                word_dst[:, pl.ds(at, lanes)] = word
            return carry

        steps = (out_hi_ref.shape[1] if last else body) // lanes
        lax.fori_loop(zero, jnp.int32(steps), chunk, zero)


def _compact_pallas(hi, lo, ok, dim: int, *, interpret: bool = False):
    """``_first_accepted`` as the kernel ``chacha_compact``: ``(P, window)``
    word pairs and accepted flags -> ``(P, dim)`` pairs, the same bits.
    Nothing is padded or cut in HBM: a block is eight rows by
    ``_compact_plan``'s lanes, wider than the arrays, so the pipeline copies
    what there is of a tile and the kernel takes the rest for "no draw"."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .jaxcfg import I32_ZERO as zero  # literal 0 would trace as i64

    rows, window = ok.shape
    stages, lanes_in, body, scratch, lanes_out, vmem = _compact_plan(window, dim)
    row_tile = lambda lanes: pl.BlockSpec((8, lanes), lambda i: (i, zero))
    out = jax.ShapeDtypeStruct((rows, dim), jnp.uint32)
    return pl.pallas_call(
        functools.partial(_compact_kernel, stages=stages, window=window, dim=dim, body=body),
        grid=(-(-rows // 8),),
        in_specs=[row_tile(lanes_in)] * 3,
        out_specs=[row_tile(lanes_out)] * 2,
        out_shape=[out, out],
        scratch_shapes=[
            pltpu.VMEM((8, scratch), jnp.uint32),
            pltpu.VMEM((8, scratch), jnp.uint32),
            pltpu.VMEM((8, scratch), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=vmem + (8 << 20)
        ),
        interpret=interpret,
        name="chacha_compact",
    )(hi, lo, ok.astype(jnp.int32))


def count_compaction(path: str, rows: int) -> None:
    """``rows`` more keystream rows compacted by ``path``. The path is a
    property of the traced program, as a limb sum's road is
    (``parallel.sumfirst.count_limb_sum_road``): counted where it is chosen,
    once for each expansion of a trace."""
    telemetry.counter(
        "sda_crypto_chacha_compactions_total",
        "keystream rows compacted to their first dim accepted draws, by path "
        "(pallas | interpret | jnp)",
        path=path,
    ).inc(rows)


def _compact(hi, lo, ok, dim: int, backend: str):
    """:func:`_first_accepted` by backend name, as ``_rounds`` dispatches the
    rounds: the kernel where the program is compiled for a TPU (``auto``
    decides where it is lowered; counted as this process's backend, which is
    what runs it), its source on the interpreter, or the compaction in XLA. A
    shape the kernel is not worth or cannot hold (``_compact_fits``) takes
    XLA's under every name."""
    from jax import lax

    rows, window = ok.shape
    if backend == "jnp" or not _compact_fits(window, dim):
        count_compaction("jnp", rows)
        return _first_accepted(hi, lo, ok, dim)
    count_compaction(default_backend() if backend == "auto" else backend, rows)
    if backend == "auto":
        return lax.platform_dependent(
            hi,
            lo,
            ok,
            tpu=functools.partial(_compact_pallas, dim=dim),
            default=functools.partial(_first_accepted, dim=dim),
        )
    return _compact_pallas(hi, lo, ok, dim, interpret=backend == "interpret")


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
    import jax.numpy as jnp

    from .modular import mod_u64_const

    seed_words = jnp.asarray(seed_words, dtype=jnp.uint32)
    P = seed_words.shape[0]
    if P == 0:
        return jnp.zeros((0, dim), dtype=jnp.int64), jnp.zeros((0,), dtype=jnp.int32)
    zone = rand03_zone(modulus)  # rand-0.3 exact: rejection always applies
    need_pairs = _window_pairs(dim, modulus)
    n_blocks = (need_pairs * 2 + 15) // 16
    words = _rounds(seed_words, n_blocks, 0, backend).reshape(P, n_blocks * 16)
    # a draw is (high word, low word); the two stay apart, in the chip's own
    # 32-bit lanes, until the first ``dim`` accepted ones are picked
    hi, lo = words[:, 0::2], words[:, 1::2]
    zone_hi, zone_lo = jnp.uint32(zone >> 32), jnp.uint32(zone & 0xFFFFFFFF)
    ok = (hi < zone_hi) | ((hi == zone_hi) & (lo < zone_lo))
    counts = jnp.sum(ok, axis=1).astype(jnp.int32)
    hi, lo = _compact(hi, lo, ok, dim, backend)
    # the accepted draws mod p, still as word pairs: no division on the device
    masks = mod_u64_const(hi, lo, modulus).astype(jnp.int64)
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


@functools.lru_cache(maxsize=None)
def fold_chunk_mesh_jit(mesh):
    """The recipient's jitted fold over ``mesh``: ``fn(seeds (chips * P, w)
    uint32, dim, modulus, backend) -> ((dim,) partial mask sum mod m of all of
    them, (chips, dim) every chip's own partial, (chips * P,) accepted
    counts)``, the last three arguments static. The seeds are sharded over all
    of the mesh's axes, ``P`` a chip in the mesh's device order; every chip
    runs :func:`_fold_chunk`, both kernels, on the seeds of its own shard, and
    the chips' partials meet under ``fabric.unmask/meet``: gathered, then
    summed by ``mod_sum_wide_jnp``, whose pair sums stay under 2m (a plain
    int64 ``psum`` of eight canonical 61-bit partials is 2^63). The met sum is
    on every chip; a chip's own partial and its counts stay where they were
    made. One program a mesh, public as :func:`fold_chunk_jit` is."""
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from .modular import mod_sum_wide_jnp

    axes = mesh.axis_names

    def _fold_chunk_mesh(batch, dim: int, modulus: int, backend: str):
        def local_fold(seeds):
            part, counts = _fold_chunk(seeds, dim, modulus, backend)
            with jax.named_scope("fabric.unmask/meet"):
                met = mod_sum_wide_jnp(lax.all_gather(part, axes), modulus, axis=0)
            return met, part[None], counts

        return jax.shard_map(
            local_fold,
            mesh=mesh,
            in_specs=P(axes),
            out_specs=(P(), P(axes), P(axes)),
            check_vma=False,
        )(batch)

    return jax.jit(_fold_chunk_mesh, static_argnums=(1, 2, 3))


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
#: expansion materializes ~5 chunk x dim x 8 B tensors at peak (the
#: keystream, its word pairs and their shifts, the compacted pairs, the final
#: masks) where the states are built in XLA too; with both kernels the
#: compiler reserves 1.05e9 B for a fold of 500 x 100 000 (3.86e9 before PR 40)
_COMBINE_BYTES_BUDGET = 2 << 30


def count_fold_chips(chips: int) -> None:
    """One more device fold of the recipient's combine, on ``chips`` chips."""
    telemetry.counter(
        "sda_crypto_chacha_folds_total", "device folds of the recipient's combine"
    ).inc()
    telemetry.counter(
        "sda_crypto_chacha_fold_chips_total",
        "chips the recipient's device folds ran on, added a fold",
    ).inc(chips)


def _host_fold(batch, dim: int, modulus: int):
    """The partial mask sum of ``batch`` by the host path, which extends a
    seed's stream on demand: what recovers a fold whose window came short
    (~1e-9 a row), and folds the few seeds that do not divide over a mesh."""
    import numpy as np

    from .chacha import expand_seed
    from .modular import mod_sum_wide_np

    masks = np.stack([expand_seed(s, dim, modulus) for s in batch])
    return mod_sum_wide_np(masks, modulus, axis=0)


@functools.lru_cache(maxsize=None)
def _own_rows_jit(mesh, rows: int):
    """``fn(seeds, start) -> seeds``: of ``(n, w)`` seeds sharded over all of
    ``mesh``'s axes, every chip's own rows ``start`` to ``start + rows``, as
    sharded; nothing crosses. One program a mesh and a slice length."""
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    by_rows = P(mesh.axis_names)
    return jax.jit(
        jax.shard_map(
            lambda seeds, start: lax.dynamic_slice_in_dim(seeds, start, rows),
            mesh=mesh, in_specs=(by_rows, P()), out_specs=by_rows, check_vma=False,
        )
    )


def _mesh_batches(seed_words, chunk: int, mesh):
    """``(batches, rest)``: what :func:`combine_masks_device` folds over
    ``mesh``, a call a batch (``(rows, w)`` uint32 device arrays sharded over
    all the mesh's axes, at most ``chunk`` rows a chip), and the host rows that
    are left: the fewer than ``mesh.size`` seeds that do not divide over the
    chips. Host rows are put sharded, ``chunk`` a chip a call. Device arrays (a
    list of them, each sharded over the mesh by rows) are folded where they
    lie: one of at most ``chunk`` rows a chip as it is, a longer one as slices
    of every chip's own rows."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    chips = mesh.size
    if not isinstance(seed_words, (list, tuple)):
        seed_words = np.asarray(seed_words, dtype=np.uint32)
        sharded = NamedSharding(mesh, P(mesh.axis_names))
        whole = seed_words.shape[0] - seed_words.shape[0] % chips
        batches = [
            jax.device_put(seed_words[start : min(start + chunk * chips, whole)], sharded)
            for start in range(0, whole, chunk * chips)
        ]
        return batches, seed_words[whole:]
    batches = []
    for seeds in seed_words:
        local, over = divmod(seeds.shape[0], chips)
        if over:
            raise ValueError(
                f"{seeds.shape[0]} seeds on the device do not divide over {chips} chips"
            )
        if local <= chunk:
            batches.append(seeds)
            continue
        for start in range(0, local, chunk):
            own_rows = _own_rows_jit(mesh, min(chunk, local - start))
            batches.append(own_rows(seeds, np.int32(start)))
    return batches, np.zeros((0, 0), np.uint32)


def _combine_over_mesh(seed_words, dim: int, modulus: int, chunk: int, backend: str, mesh):
    """:func:`combine_masks_device` over ``mesh``: ``(the (dim,) combined mask,
    on every chip of the mesh; the seeds folded)``."""
    import jax.numpy as jnp
    import numpy as np

    from .modular import mod_sum_wide_np

    chips = mesh.size
    fold = fold_chunk_mesh_jit(mesh)
    batches, rest = _mesh_batches(seed_words, chunk, mesh)
    total = jnp.zeros((dim,), dtype=jnp.int64)
    for batch in batches:
        met, parts, counts = fold(batch, dim, modulus, backend)
        count_fold_chips(chips)
        short = np.asarray(counts).reshape(chips, -1) < dim
        if short.any():
            count_slack_exhausted("recipient", int(short.sum()))
            logging.getLogger(__name__).info(
                "rejection slack exhausted on %d chip(s); host-expanding their batches",
                int(short.any(axis=1).sum()),
            )
            parts = np.array(parts)
            own = np.asarray(batch).reshape(chips, -1, batch.shape[1])
            for chip in np.flatnonzero(short.any(axis=1)):
                parts[chip] = _host_fold(own[chip], dim, modulus)
            met = jnp.asarray(mod_sum_wide_np(parts, modulus, axis=0))
        total = (total + met) % jnp.int64(modulus)
    if rest.shape[0]:
        total = (total + _host_fold(rest, dim, modulus)) % jnp.int64(modulus)
    return total, sum(int(batch.shape[0]) for batch in batches) + int(rest.shape[0])


def combine_masks_device(
    seed_words,
    dim: int,
    modulus: int,
    *,
    chunk: int | None = None,
    backend: str = "auto",
    mesh=None,
):
    """Recipient reveal hot loop on device: Σ_p expand(seed_p) mod m.

    (P, w) uint32 seeds -> (dim,) int64 combined mask — the ChaCha
    ``SecretUnmasker``'s inner sum (reference chacha.rs:56-77) as a device
    computation, folding ``chunk`` seeds at a time. The default chunk is
    sized so one fold's ~5 transient chunk x dim x 8 B tensors fit in
    ``_COMBINE_BYTES_BUDGET`` (e.g. dim=100K -> chunk ~ 1K folds of ~2 GB),
    so the headline 1M x 100K reveal streams instead of OOMing.
    ``backend`` as in ``_rounds``.

    Given a ``mesh`` (a masked round's: ``parallel.round.FoldRound.unmask``),
    a fold takes ``chunk`` seeds *a chip* and runs on every chip of it
    (:func:`fold_chunk_mesh_jit`): host rows are put sharded, and
    ``seed_words`` may instead be a list of ``(rows, w)`` device arrays that
    are sharded over the mesh already, which are folded where they lie
    (:func:`_mesh_batches`). A short window on any chip is counted as on one
    chip, and that chip's batch alone recovered on the host.
    """
    from .jaxcfg import ensure_x64

    ensure_x64()
    import jax.numpy as jnp
    import numpy as np

    if chunk is None:
        chunk = max(16, _COMBINE_BYTES_BUDGET // (5 * 8 * dim))
    if backend == "auto":  # resolved here: it is a static jit argument
        backend = default_backend()
    # same series the host paths count into (native/__init__.py), so a
    # scrape shows which implementation expanded a reveal's seeds
    expands = telemetry.counter(
        "sda_crypto_chacha_expands_total",
        "ChaCha mask seeds expanded/combined by path",
        path=backend,
    )
    if mesh is not None:
        total, seeds = _combine_over_mesh(seed_words, dim, modulus, chunk, backend, mesh)
        expands.inc(seeds)
        return total
    seed_words = np.asarray(seed_words, dtype=np.uint32)
    expands.inc(int(seed_words.shape[0]))

    fold = fold_chunk_jit()
    total = jnp.zeros((dim,), dtype=jnp.int64)
    for start in range(0, seed_words.shape[0], chunk):
        batch = seed_words[start : start + chunk]
        part, counts = fold(jnp.asarray(batch), dim, modulus, backend)
        count_fold_chips(1)
        if counts.shape[0] and int(jnp.min(counts)) < dim:
            count_slack_exhausted("recipient", int(jnp.sum(counts < dim)))
            logging.getLogger(__name__).info(
                "rejection slack exhausted in chunk at %d; host-expanding it", start
            )
            # ~1e-9-per-row event: host-expand just this chunk and keep the
            # device fold going
            part = jnp.asarray(_host_fold(batch, dim, modulus))
        total = (total + part) % jnp.int64(modulus)
    return total
