#!/usr/bin/env python3
"""Step 0 of a change to the per-participant engine, on the chip, at
``c4-participant``'s shapes (a chunk of 2000 x 50 000 int32, the config's
31-bit prime, k 5, t 2, n 8), seconds a call each:

(a) the fold in XLA's formulation (``engine.share_combine_limb_xla``: the
    parent's whole step);
(b) the de-interleave of the chunk into k planes of ``nb`` lanes, alone, by
    each of: ``reshape(C, nb, k)`` + transpose, k strided slices
    ``secrets[:, s::k]`` (a gather to jnp), the same as ``lax.slice`` with a
    stride, and a 0/1 permutation matmul on int8 limb tiles of 640 lanes;
    and the transpose alone, which leaves the stride to the kernel's loads;
(c) the kernel ``limb_share_combine`` alone on ready values: the tree's (the
    chunk transposed, participants on the lanes), and with ``--parent-kernel
    <limb_pallas.py>`` that file's (PR 21's: (C, K, nb) planes, a participant
    at a time, L dots of (n, 8L) @ (8L, tile)) as written and with its L dots
    merged into one (L*n, 8L) @ (8L, tile);
(d) the tree's ``share_combine_limb`` whole, held to (a) bit for bit for one
    key, with its largest operations by the profiler's trace; and what
    ``sda_fabric_share_combine_total{path}`` counted on the way.

    chiprun -- python scripts/chip_limb_step0.py \
        [--parent-kernel .archive_tree/parent/sda_tpu/parallel/limb_pallas.py]

One JSON line; also ``chiprun_out/limb-step0.json``. Exit 1 where a kernel's
bits differ from XLA's formulation. ``--rehearse`` runs it off the chip at a
small shape, kernels on the interpreter, no trace: its times mean nothing.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "scripts"))

from chip_fold_step0 import largest_operations, timed  # noqa: E402

K, T, N = 5, 2, 8
GROUP = 128 * K  # lanes one permutation tile de-interleaves


def deinterleave_reshape(secrets, nb):
    import jax.numpy as jnp

    C = secrets.shape[0]
    return jnp.transpose(secrets.reshape(C, nb, K), (2, 0, 1))


def deinterleave_slices(secrets, nb):
    """``secrets[:, s::k]`` as jnp indexes it: a gather, which the compiler
    turns into a transpose of the chunk, k slices by rows, k transposes."""
    import jax.numpy as jnp

    return jnp.stack([secrets[:, s::K] for s in range(K)])


def deinterleave_lax_slices(secrets, nb):
    """The same k slices as ``lax.slice`` with a stride: one strided slice
    operation each, along the lanes, nothing transposed."""
    import jax.numpy as jnp
    from jax import lax

    C, d = secrets.shape
    return jnp.stack([lax.slice(secrets, (0, s), (C, d), (1, K)) for s in range(K)])


def deinterleave_permutation(secrets, nb, limbs):
    """Lane 5b+s of a 640-lane group -> lane 128s+b, by an int8 matmul of
    each 7-bit limb with a 0/1 matrix; the limbs joined again in int32."""
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    C, d = secrets.shape
    groups = -(-d // GROUP)
    x = jnp.pad(secrets, ((0, 0), (0, groups * GROUP - d))).reshape(C, groups, GROUP)
    perm = np.zeros((GROUP, GROUP), np.int8)
    lane = np.arange(GROUP)
    perm[lane, (lane % K) * 128 + lane // K] = 1
    out = None
    for i in range(limbs):
        limb = ((x >> jnp.int32(7 * i)) & jnp.int32(0x7F)).astype(jnp.int8)
        moved = lax.dot_general(
            limb, jnp.asarray(perm), (((2,), (0,)), ((), ())), preferred_element_type=jnp.int32
        ) << jnp.int32(7 * i)
        out = moved if out is None else out | moved
    planes = jnp.transpose(out.reshape(C, groups, K, 128), (2, 0, 1, 3))
    return planes.reshape(K, C, groups * 128)[:, :, :nb]


def merged_dot_sums(values, stacks, parent, *, interpret):
    """``parent.participant_limb_sums_pallas`` with its L dots a participant
    merged into one: (C, K, nb) planes -> (L, n, nb)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows_of, tile_of, block_bytes = parent._ROWS, parent._NB_TILE, parent._BLOCK_BYTES
    C, k_rows, nb = values.shape
    L, _, n = stacks.shape
    tile = min(tile_of, -(-nb // 128) * 128)
    nb_p = -(-nb // tile) * tile
    block_c = min(C, max(1, block_bytes // (rows_of * tile * 4)))
    c_p = -(-C // block_c) * block_c
    values = jnp.pad(values, ((0, c_p - C), (0, rows_of - k_rows), (0, nb_p - nb)))
    rows = np.zeros((L, n, L * rows_of), dtype=np.int8)
    for i in range(L):
        rows[:, :, i * rows_of : i * rows_of + k_rows] = np.swapaxes(
            stacks[:, i * k_rows : (i + 1) * k_rows, :], 1, 2
        )
    from sda_tpu.ops.jaxcfg import I32_ZERO as zero  # a literal 0 would trace as i64

    def kernel(values_ref, rows_ref, out_ref):
        @pl.when(pl.program_id(1) == 0)
        def _():
            out_ref[...] = jnp.zeros_like(out_ref)

        def one_participant(c, carry):
            x = values_ref[c]
            a = jnp.concatenate(
                [(x >> jnp.int32(7 * i)) & jnp.int32(0x7F) for i in range(L)], axis=0
            ).astype(jnp.int8)
            out_ref[...] += lax.dot_general(
                rows_ref[...], a, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
            )
            return carry

        lax.fori_loop(zero, jnp.int32(block_c), one_participant, zero)

    out = pl.pallas_call(
        kernel,
        grid=(nb_p // tile, c_p // block_c),
        in_specs=[
            pl.BlockSpec((block_c, rows_of, tile), lambda b, j: (j, zero, b)),
            pl.BlockSpec((L * n, L * rows_of), lambda b, j: (zero, zero)),
        ],
        out_specs=pl.BlockSpec((L * n, tile), lambda b, j: (zero, b)),
        out_shape=jax.ShapeDtypeStruct((L * n, nb_p), jnp.int32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="limb_share_combine_merged",
    )(values, jnp.asarray(rows.reshape(L * n, L * rows_of)))
    return out.reshape(L, n, nb_p)[:, :, :nb]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=2000)
    ap.add_argument("--dim", type=int, default=50_000)
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--parent-kernel", default=None, help="PR 21's limb_pallas.py, to time as written and merged")
    ap.add_argument("--rehearse", action="store_true", help="run off the chip: no trace, times mean nothing")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from sda_tpu.ops import find_packed_parameters
    from sda_tpu.ops.jaxcfg import ensure_x64
    from sda_tpu.parallel import engine, limb_pallas
    from sda_tpu.parallel.limbmatmul import fold_const_limbs, limb_count
    from sda_tpu.protocol import PackedShamirSharing

    ensure_x64()
    on_chip = jax.default_backend() == "tpu"
    if not on_chip and not args.rehearse:
        print("no TPU: this measures nothing elsewhere", file=sys.stderr)
        return 2
    p, w2, w3 = find_packed_parameters(K, T, N, min_modulus_bits=30, seed=0)
    plan = engine.make_plan(PackedShamirSharing(K, N, T, p, w2, w3), args.dim)
    C, nb, L = args.rows, plan.n_batches, limb_count(p)
    stacks = fold_const_limbs(plan.share_matrix.T, p)
    secrets = jnp.asarray(
        np.random.default_rng(38).integers(0, p, size=(C, args.dim)).astype(np.int32)
    )
    key = jax.random.key(38)
    out = {"rows": C, "dim": args.dim, "modulus_bits": p.bit_length(), "rehearsal": not on_chip}
    out["device"] = jax.devices()[0].device_kind
    trace = largest_operations if on_chip else (lambda *a, **k: None)
    ok = True

    def seconds(fn, *a):
        first, each = timed(jax.jit(fn), *a, calls=args.calls)
        return {"first_call_s": first, "s": each}

    # (a) XLA's formulation
    xla = jax.jit(lambda s, kk: engine.share_combine_limb_xla(s, kk, plan))
    out["xla_step"] = seconds(xla, secrets, key)
    want = np.asarray(xla(secrets, key))

    # (b) the de-interleave alone, (k, C, nb)
    padded = jnp.pad(secrets, ((0, 0), (0, nb * K - args.dim)))
    builds = {
        "reshape_transpose": lambda s: deinterleave_reshape(s, nb),
        "strided_slices": lambda s: deinterleave_slices(s, nb),
        "strided_lax_slices": lambda s: deinterleave_lax_slices(s, nb),
        "permutation_matmul": lambda s: deinterleave_permutation(s, nb, L),
    }
    planes = None
    for name, build in builds.items():
        out[f"deinterleave.{name}"] = seconds(build, padded)
        got = jax.jit(build)(padded)
        if planes is None:
            planes = got
        elif not bool(jnp.array_equal(got, planes)):
            out[f"deinterleave.{name}"]["differs"] = True
            ok = False
    del got
    # what the tree keeps: the chunk transposed, the stride left to the kernel's loads
    out["deinterleave.transpose_only"] = seconds(lambda s: s.T, padded)

    # (c) the kernel alone, on ready planes of canonical values
    rand = jnp.asarray(
        np.random.default_rng(39).integers(0, p, size=(T, C, nb)).astype(np.int32)
    )
    ready = jnp.concatenate([planes, rand])  # (K+T, C, nb)
    del planes, rand
    kernel_want = np.asarray(_plain_sums(ready, stacks, p))  # (L, nb, n)

    def kernel_leg(name, fn, *values, batch_axis):
        nonlocal ok
        jitted = jax.jit(fn)
        out[name] = seconds(jitted, *values)
        out[name]["trace"] = trace(jitted, *values, calls=2, top=3)
        got = np.moveaxis(np.asarray(jitted(*values)), batch_axis, 1)
        out[name]["equals_xla"] = same = np.array_equal(got, kernel_want)
        ok = ok and same

    interpret = not on_chip
    # the tree's: the chunk transposed, (nb*k, C), and the draw as (t, nb, C)
    kernel_leg(
        "kernel.tree",
        lambda c, r: limb_pallas.participant_limb_sums_pallas(c, r, stacks, interpret=interpret),
        jnp.asarray(np.asarray(padded).T),
        jnp.swapaxes(ready[K:], 1, 2),
        batch_axis=1,
    )
    if args.parent_kernel:
        spec = importlib.util.spec_from_file_location(
            "sda_tpu.parallel.limb_pallas_parent", args.parent_kernel
        )
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)
        by_participant = jnp.swapaxes(ready, 0, 1)  # (C, K+T, nb)
        kernel_leg(
            "kernel.as_written",
            lambda v: parent.participant_limb_sums_pallas(v, stacks, interpret=interpret),
            by_participant,
            batch_axis=2,
        )
        kernel_leg(
            "kernel.merged_dot",
            lambda v: merged_dot_sums(v, stacks, parent, interpret=interpret),
            by_participant,
            batch_axis=2,
        )
        del by_participant
    del ready

    # (d) the tree's entry whole, against (a)
    entry = (
        (lambda s, kk: limb_pallas.share_combine_limb_pallas(s, kk, plan, interpret=True))
        if interpret
        else (lambda s, kk: engine.share_combine_limb(s, kk, plan))
    )
    step = jax.jit(entry)
    out["step"] = seconds(step, secrets, key)
    out["step"]["trace"] = trace(step, secrets, key, calls=2, top=12)
    out["step"]["equals_xla"] = same = np.array_equal(np.asarray(step(secrets, key)), want)
    ok = ok and same

    # which layout the traces above chose: ``fused`` on a chip, for (d)
    from sda_tpu import telemetry

    out["share_combine_total"] = {
        dict(labels)["path"]: value
        for (name, labels), value in telemetry.get_registry().snapshot()["counters"].items()
        if name == "sda_fabric_share_combine_total"
    }
    line = json.dumps(out)
    os.makedirs(REPO / "chiprun_out", exist_ok=True)
    (REPO / "chiprun_out" / "limb-step0.json").write_text(line + "\n")
    print(line)
    return 0 if ok else 1


def _plain_sums(ready, stacks, p):
    """The kernel's sums by XLA's operations: (K+T, C, nb) -> (L, nb, n)."""
    import jax
    import jax.numpy as jnp

    from sda_tpu.parallel.limbmatmul import limb_partials_const

    rows, C, nb = ready.shape
    L, n = stacks.shape[0], stacks.shape[2]
    values = jnp.transpose(ready, (1, 2, 0)).reshape(C * nb, rows)
    return jax.jit(
        lambda v: jnp.sum(limb_partials_const(v, stacks, p).reshape(L, C, nb, n), axis=1)
    )(values)


if __name__ == "__main__":
    sys.exit(main())
