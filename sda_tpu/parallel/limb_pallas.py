"""Pallas TPU kernel: fused per-participant limb share matmul + reduce.

The per-participant engine path (``engine.share_combine_limb``) computes
every participant's share limb-partials individually — (L, C·nb, n) int32
— and then reduces over participants. Under XLA those partials round-trip
HBM between the dot and the reduction. This kernel fuses them: each grid
step loads one block of participants, runs each participant's L
const-folded limb dots (``limbmatmul.fold_const_limbs``) on the MXU and
accumulates them into the small (L, n, nb) output in VMEM — per-participant
shares exist (transiently, like the reference's per-phone loop) but never
touch HBM.

Layout: the batch axis ``nb`` rides the 128-wide lanes and a participant's
``K = k+t`` value rows ride the sublanes (padded to 8, one int32 tile
row), so every block is lane-dense whatever ``K`` is. The limb operand of
the dot is the sublane concatenation of the L limb planes, ``(8·L, tile)``
int8, against the ``(n, 8·L)`` folded share rows; the grid walks batch
tiles (outer, independent) and participant blocks (inner, accumulating).
Zero padding on every axis is exact: a zero value has zero limbs.

Everything in-kernel is int32: partials are bounded by L·K·127² and the
participant accumulation by C_total·L·K·127², which must stay < 2^31
(checked at trace time — a chunk of 2000 is well inside). The
mod-p recombine (int64 multiply + one rem) happens outside on the reduced
accumulator, exactly like the jnp path.

Narrow fields only (p < 2^31: int32 limb extraction); the wide path keeps
the jnp formulation. Tests run the kernel source under the Pallas
interpreter (``interpret=True``); nothing here chooses that by itself.
"""

from __future__ import annotations

import numpy as np

from ..ops.jaxcfg import I32_ZERO as _Z  # literal 0 would trace as i64
from ..ops.jaxcfg import ensure_x64
from .limbmatmul import fold_const_limbs

#: sublane rows one participant's K values are padded to (one int32 tile)
_ROWS = 8
#: widest batch tile on the lane axis (a multiple of 128)
_NB_TILE = 2048
#: VMEM one value block may take; Pallas double-buffers it, and the scoped
#: default on a v5e is 16 MiB
_BLOCK_BYTES = 2 << 20


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def participant_limb_sums_pallas(values, stacks, *, interpret: bool = False):
    """(C, K, nb) int32 canonical values -> (L, n, nb) int32 partial sums.

    ``stacks`` from ``fold_const_limbs`` (L, L*K, n) int8. Same sums as
    ``limb_partials_const`` + participant reduction (weights 128^m), with
    the batch axis last on both sides.
    """
    ensure_x64()
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    C, K, nb = values.shape
    L, LK, n = stacks.shape
    if LK != L * K:
        raise ValueError(f"stacks contraction {LK} != L*K = {L * K}")
    if K > _ROWS:
        raise ValueError(f"K = {K} value rows exceed the {_ROWS}-row tile")
    if C * LK * 127 * 127 >= (1 << 31):
        raise ValueError(
            f"participant accumulation over C={C} overflows int32; chunk first"
        )
    tile = min(_NB_TILE, _round_up(nb, 128))
    nb_p = _round_up(nb, tile)
    block_c = min(C, max(1, _BLOCK_BYTES // (_ROWS * tile * 4)))
    c_p = _round_up(C, block_c)
    values = jnp.pad(values, ((0, c_p - C), (0, _ROWS - K), (0, nb_p - nb)))

    # (L, L*K, n) -> (L, n, L*8): limb plane i's K rows sit at columns
    # [8i, 8i+K), matching the kernel's sublane concatenation
    rows = np.zeros((L, n, L * _ROWS), dtype=np.int8)
    for i in range(L):
        rows[:, :, i * _ROWS : i * _ROWS + K] = np.swapaxes(
            stacks[:, i * K : (i + 1) * K, :], 1, 2
        )

    def kernel(values_ref, rows_ref, out_ref):
        @pl.when(pl.program_id(1) == 0)
        def _():
            out_ref[...] = jnp.zeros_like(out_ref)

        def one_participant(c, carry):
            x = values_ref[c]  # (8, tile) int32 canonical
            a = jnp.concatenate(
                [(x >> jnp.int32(7 * i)) & jnp.int32(0x7F) for i in range(L)],
                axis=0,
            ).astype(jnp.int8)  # (8L, tile)
            for m in range(L):
                out_ref[m] += lax.dot_general(
                    rows_ref[m],
                    a,
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.int32,
                )  # (n, tile)
            return carry

        # int32 bounds: Python ints would trace as i64 under x64
        lax.fori_loop(jnp.int32(0), jnp.int32(block_c), one_participant, _Z)

    out = pl.pallas_call(
        kernel,
        grid=(nb_p // tile, c_p // block_c),
        in_specs=[
            pl.BlockSpec(
                (block_c, _ROWS, tile),
                lambda b, j: (j, _Z, b),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (L, n, L * _ROWS), lambda b, j: (_Z, _Z, _Z), memory_space=pltpu.VMEM
            ),
        ],
        out_specs=pl.BlockSpec(
            (L, n, tile), lambda b, j: (_Z, _Z, b), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((L, n, nb_p), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
        name="limb_share_combine",
    )(values, jnp.asarray(rows))
    return out[:, :, :nb]


def share_combine_limb_pallas(secrets, key, plan, *, interpret: bool = False):
    """Fused-kernel twin of ``engine.share_combine_limb`` for p < 2^31:
    same (W, b, n) int64 contract (weights 128^m), bit-identical results
    for the same key."""
    ensure_x64()
    import jax.numpy as jnp

    from .engine import _batch_secrets, _device_randomness

    p = plan.modulus
    if p >= (1 << 31):
        raise ValueError("pallas participant path is narrow-field only (p < 2^31)")
    batches = _batch_secrets(secrets, plan)  # (C, b, k)
    C, nb = batches.shape[0], batches.shape[1]
    randomness = _device_randomness(key, (C, nb, plan.rand_size), p)
    values = jnp.concatenate(
        [batches.astype(jnp.int32), randomness.astype(jnp.int32)], axis=-1
    )
    stacks = fold_const_limbs(plan.share_matrix.T, p)
    acc = participant_limb_sums_pallas(
        jnp.swapaxes(values, 1, 2), stacks, interpret=interpret
    )  # (L, n, b)
    return jnp.swapaxes(acc, 1, 2).astype(jnp.int64)  # (W=L, b, n)
