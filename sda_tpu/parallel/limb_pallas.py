"""Pallas TPU kernel: fused per-participant limb share matmul + reduce.

The per-participant engine (``engine.share_combine_limb``) multiplies every
participant's ``K = k+t`` value rows by the share matrix in limb space and
sums the partial products over the participants. In XLA's formulation
(``engine.share_combine_limb_xla``) the per-participant partials, (L, C·nb,
n) int32, round-trip HBM between the dot and the reduction, and every tensor
on the way has the 20-million axis ``C·nb`` major and 7, 35 or 8 minor, on a
machine whose tiles are 128 lanes wide. This kernel never forms that axis:
participants ride the lanes, batches the sublanes, and a participant's
partials never leave the MXU's accumulator.

Layout. The chunk comes transposed, ``(nb·k, C)`` int32: the dim axis down
the sublanes, where the de-interleave is a load's stride (value ``s`` of
batches ``b0..`` is rows ``k·b0 + s`` step ``k``) and costs nothing; the draw
as ``(t, nb, C)``, a layout to the compiler that makes it. A grid step holds
``_BATCHES`` batches of ``_LANES`` = 128 participants: the K·L limb planes
(7-bit, int8) of their values are laid side by side along the lanes of one
``(_BATCHES, K·L·128)`` operand and met by **one** dot with the constant
``(K·L·128, L·n)`` share rows, every participant's copy of a coefficient one
under the other: the MXU holds the constant side, streams the batches,
multiplies each participant's limb by its coefficient and adds the 128
participants in its own accumulator. The ``(_BATCHES, L·n)`` result
is added into the output block, resident in VMEM while the grid walks the
participant blocks (inner, ``arbitrary``) of one batch tile (outer,
``parallel``). Nothing is padded in HBM: the last participant block zeroes
the lanes past C as it reads them (a zero value has zero limbs), and the
last batch tile's rows past nb never reach the output.

Everything in-kernel is int32: a dot's elements are bounded by 128·L·K·127²
and the accumulation by C·L·K·127², which must stay < 2^31
(``fused_fits``: a chunk of 2000 is well inside). The mod-p recombine (int64
multiply + one rem) happens outside on the reduced accumulator, exactly like
the XLA formulation.

Narrow fields only (p < 2^31: int32 limb extraction). Nothing here chooses
the interpreter by itself: ``engine.share_combine_limb`` takes the compiled
kernel where its program is lowered for a TPU, and tests pass
``interpret=True`` to :func:`share_combine_limb_pallas`.
"""

from __future__ import annotations

import numpy as np

from ..ops.jaxcfg import I32_ZERO as _Z  # literal 0 would trace as i64
from ..ops.jaxcfg import ensure_x64
from .limbmatmul import limb_count

#: most value rows K = k+t a block is sized for
MAX_ROWS = 8
#: participants a grid step loads and one dot contracts: the lanes of a tile
#: (Mosaic's strided load wants a block exactly that wide)
_LANES = 128
#: batches a grid step loads (a multiple of 32, a packed int8 tile's sublanes)
_BATCHES = 512


def fused_fits(modulus: int, participants: int, rows: int) -> bool:
    """Whether the kernel can fold a chunk of ``participants`` with ``rows``
    = k+t value rows each over this field: int32 limb extraction, a block the
    VMEM budget was sized for, and an int32 accumulation over the chunk."""
    contraction = limb_count(modulus) * rows
    return (
        modulus < (1 << 31)
        and rows <= MAX_ROWS
        and participants * contraction * 127 * 127 < (1 << 31)
    )


def _share_rows(stacks, K: int) -> np.ndarray:
    """``fold_const_limbs``' (L, L·K, n) stacks as the dot's constant side,
    (K·L·_LANES, L·n) int8: row ``(s·L + i)·_LANES + c``, column ``m·n +
    clerk`` holds limb ``m`` of ``128^i · share[s, clerk]`` for every
    participant ``c`` of a contraction group."""
    L, _, n = stacks.shape
    by_limb = np.asarray(stacks).reshape(L, L, K, n)  # [m, i, s, clerk]
    rows = np.transpose(by_limb, (2, 1, 0, 3)).reshape(K * L, L * n)  # [(s, i), (m, clerk)]
    return np.repeat(rows, _LANES, axis=0)


def participant_limb_sums_pallas(by_dim, draws, stacks, *, interpret: bool = False):
    """A chunk's canonical values, participants on the lanes -> (L, nb, n)
    int32 partial sums over the C participants.

    ``by_dim`` (nb·k, C) int32: the chunk transposed, its dim axis padded
    to whole batches; ``draws`` (t, nb, C) int32; ``stacks`` from
    ``fold_const_limbs`` (L, L·(k+t), n) int8. Same sums as
    ``limb_partials_const`` + participant reduction (weights 128^m).
    """
    ensure_x64()
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t, nb, C = draws.shape
    L, LK, n = stacks.shape
    K = LK // L
    k = K - t
    if LK != L * K or k < 1 or by_dim.shape != (nb * k, C):
        raise ValueError(
            f"stacks {stacks.shape}, draws {draws.shape} and the chunk {by_dim.shape} "
            "do not agree on k, t, nb and C"
        )
    if K > MAX_ROWS:
        raise ValueError(f"K = {K} value rows exceed the {MAX_ROWS} a block is sized for")
    if C * LK * 127 * 127 >= (1 << 31):
        raise ValueError(
            f"participant accumulation over C={C} overflows int32; chunk first"
        )
    batches = min(_BATCHES, -(-nb // 32) * 32)
    blocks = -(-C // _LANES)
    # lanes of the last participant block that hold participants; past them a
    # block holds whatever the edge of the array left there
    last_lanes = C - (blocks - 1) * _LANES

    def kernel(by_dim_ref, draws_ref, rows_ref, out_ref, limbs_ref):
        block = pl.program_id(1)

        @pl.when(block == 0)
        def _():
            out_ref[...] = jnp.zeros_like(out_ref)

        def fold(valid_lanes: int):
            for s in range(K):
                if s < k:  # value s of every batch: rows s, s+k, ... of the chunk's
                    x = by_dim_ref[pl.ds(s, batches, stride=k), :]
                else:
                    x = draws_ref[s - k]
                if valid_lanes < _LANES:
                    lane = lax.broadcasted_iota(jnp.int32, x.shape, 1)
                    x = jnp.where(lane < jnp.int32(valid_lanes), x, _Z)
                for i in range(L):
                    limbs_ref[:, pl.ds((s * L + i) * _LANES, _LANES)] = (
                        (x >> jnp.int32(7 * i)) & jnp.int32(0x7F)
                    ).astype(jnp.int8)
            out_ref[...] += lax.dot_general(
                limbs_ref[...],
                rows_ref[...],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )  # (batches, L*n)

        if last_lanes == _LANES:
            fold(_LANES)
        else:
            pl.when(block < blocks - 1)(lambda: fold(_LANES))
            pl.when(block == blocks - 1)(lambda: fold(last_lanes))

    # a batch tile past nb reads and writes rows the output does not have: a
    # row's sums depend on that batch alone, and the edge is cut on the way out
    out = pl.pallas_call(
        kernel,
        grid=(-(-nb // batches), blocks),
        in_specs=[
            pl.BlockSpec((batches * k, _LANES), lambda b, j: (b, j), memory_space=pltpu.VMEM),
            pl.BlockSpec((t, batches, _LANES), lambda b, j: (_Z, b, j), memory_space=pltpu.VMEM),
            pl.BlockSpec((LK * _LANES, L * n), lambda b, j: (_Z, _Z), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((batches, L * n), lambda b, j: (b, _Z), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((nb, L * n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((batches, LK * _LANES), jnp.int8)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
        name="limb_share_combine",
    )(by_dim, draws, jnp.asarray(_share_rows(stacks, K)))
    return jnp.swapaxes(out.reshape(nb, L, n), 0, 1)


def share_combine_limb_pallas(secrets, key, plan, *, interpret: bool = False):
    """``engine.share_combine_limb``'s fused layout by name, for p < 2^31:
    the same draw, the same plane builder, the same kernel, whatever the
    platform. The tests' entry (``interpret=True`` runs the kernel's source
    under the Pallas interpreter); no road of its own on the chip. Same
    (W, b, n) int64 contract (weights 128^m), bit-identical results for the
    same key."""
    from . import engine

    K = plan.input_size + plan.rand_size
    if not fused_fits(plan.modulus, secrets.shape[0], K):
        raise ValueError(
            "the fused participant path needs p < 2^31, at most "
            f"{MAX_ROWS} value rows and an int32 accumulation over the chunk"
        )
    engine.count_share_combine("interpret" if interpret else "fused")
    return engine._combine_fused(
        secrets, engine._share_draw(secrets, key, plan), plan=plan, interpret=interpret
    )
