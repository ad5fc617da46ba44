#!/usr/bin/env python3
"""Step 0 of a change to the mask's expansion, on the chip: the recipient's
jitted fold (``ops.chacha_pallas.fold_chunk_jit()``) at a cell's shapes
(seconds a call, compile seconds, temporaries, its largest operations by the
profiler's trace); the keystream's way to word pairs alone (``draws``: the
kernel ``chacha_rounds`` from the seeds, the transposition to stream order,
the split and the zone test), at each ``--rounds-plans`` pair of the kernel's
lanes a grid step and lanes a loop step, with the kernel held to the host's
bits at small shapes; and the compaction alone on the fold's own ``(rows,
window)`` word pairs: in XLA (``_first_accepted``) and in the kernel
``chacha_compact``, held to XLA's bits.

    chiprun -- python scripts/chip_fold_step0.py [--tree <checkout>] [--rounds-plans 4096,256 ...]

``--tree`` imports ``sda_tpu`` from another checkout. A tree from before PR 40
(its rounds took a states array) is timed by its own copy of this script
(``python .archive_tree/parent/scripts/chip_fold_step0.py``, its line
redirected into ``chiprun_out/``): one process a tree, a chip belongs to one
process. One JSON line; also ``chiprun_out/fold-step0-<tag>.json``.
"""

from __future__ import annotations

import argparse
import functools
import glob
import json
import os
import pathlib
import sys
import tempfile
import time

REPO = pathlib.Path(__file__).resolve().parents[1]


def timed(fn, *args, calls: int):
    """Seconds of each of ``calls`` calls after one that compiles."""
    import jax

    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t0
    seconds = []
    for _ in range(calls):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        seconds.append(time.perf_counter() - t0)
    return first, seconds


def largest_operations(fn, *args, calls: int, top: int = 8):
    """The device's busy seconds and largest operations, a call, from a trace
    of ``calls`` of them (the benchmark's own reduction)."""
    import jax

    sys.path.append(str(REPO))
    from benchmark import trace_reduce

    with tempfile.TemporaryDirectory() as log:
        with jax.profiler.trace(log):
            for _ in range(calls):
                jax.block_until_ready(fn(*args))
        (path,) = glob.glob(f"{log}/plugins/profile/*/*.xplane.pb")
        reduced = trace_reduce.reduce(trace_reduce.load_xplane(path), span_names=())
    return {
        "busy_s": reduced.max_busy_seconds() / calls,
        "largest": [[name, s / calls] for name, s in reduced.top_operations(top)],
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tree", default=str(REPO))
    ap.add_argument("--tag", default=None)
    ap.add_argument("--rows", type=int, default=500)
    ap.add_argument("--dim", type=int, default=100_000)
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument(
        "--rounds-plans", nargs="*", default=[], metavar="LANES,CHUNK",
        help="time ``draws`` under these values of the rounds kernel's two constants too",
    )
    ap.add_argument("--rehearse", action="store_true", help="run off the chip: no trace, times mean nothing")
    args = ap.parse_args()
    sys.path.insert(0, args.tree)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from sda_tpu.ops import chacha_pallas as cp
    from sda_tpu.ops import find_packed_parameters
    from sda_tpu.ops.chacha import chacha_blocks, rand03_zone
    from sda_tpu.ops.jaxcfg import ensure_x64

    ensure_x64()
    on_chip = jax.default_backend() == "tpu"
    if not on_chip and not args.rehearse:
        print("no TPU: this measures nothing elsewhere", file=sys.stderr)
        return 2
    backend = cp.default_backend()
    p = int(find_packed_parameters(5, 2, 8, min_modulus_bits=60, seed=0)[0])
    rows, dim = args.rows, args.dim
    rng = np.random.default_rng(33)
    seeds = jnp.asarray(rng.integers(0, 1 << 32, size=(rows, 4), dtype=np.uint64).astype(np.uint32))
    out = {"tree": args.tree, "rows": rows, "dim": dim, "device": jax.devices()[0].device_kind}
    out["rehearsal"] = not on_chip
    trace = largest_operations if on_chip else (lambda *a, **k: None)

    fold = cp.fold_chunk_jit()
    t0 = time.perf_counter()
    compiled = fold.lower(seeds, dim, p, backend).compile()
    out["fold_compile_s"] = time.perf_counter() - t0
    out["fold_temp_bytes"] = compiled.memory_analysis().temp_size_in_bytes
    first, seconds = timed(fold, seeds, dim, p, backend, calls=args.calls)
    out["fold_first_call_s"], out["fold_s"] = first, seconds
    out["fold_trace"] = trace(fold, seeds, dim, p, backend, calls=2)

    # the fold's own word pairs, as expand_seeds_counts makes them
    pairs = cp._window_pairs(dim, p)
    n_blocks = (pairs * 2 + 15) // 16
    zone = rand03_zone(p)

    # the kernel from the seeds, at shapes with ragged tiles, against numpy
    small = [(1, 1, 8, 0), (5, 3, 1, 5), (9, 130, 4, 0), (17, 700, 2, 5)]
    out["rounds_equal_host"] = all(
        np.array_equal(
            np.asarray(cp._rounds(jnp.asarray(keys), blocks, first, backend)),
            np.stack([chacha_blocks(k, first, blocks) for k in keys]),
        )
        for p_rows, blocks, width, first in small
        for keys in [rng.integers(0, 1 << 32, size=(p_rows, width), dtype=np.uint64).astype(np.uint32)]
    )
    plans = [(cp._ROUNDS_LANES, cp._ROUNDS_CHUNK)]
    plans += [tuple(int(v) for v in plan.split(",")) for plan in args.rounds_plans]
    out["draws"] = {}
    for lanes, chunk in plans[::-1]:  # the module's own plan last: it stays set
        cp._ROUNDS_LANES, cp._ROUNDS_CHUNK = lanes, chunk

        def draws(seed_words):  # a function a plan: jit caches by the function
            words = cp._rounds(seed_words, n_blocks, 0, backend).reshape(rows, -1)
            hi, lo = words[:, 0::2], words[:, 1::2]
            zone_hi, zone_lo = jnp.uint32(zone >> 32), jnp.uint32(zone & 0xFFFFFFFF)
            return hi, lo, (hi < zone_hi) | ((hi == zone_hi) & (lo < zone_lo))

        fn = jax.jit(draws)
        first, seconds = timed(fn, seeds, calls=args.calls)
        out["draws"][f"{lanes},{chunk}"] = {
            "grid": [-(-rows // 8), cp._rounds_plan(n_blocks)[0]],
            "first_call_s": first, "s": seconds, "trace": trace(fn, seeds, calls=2, top=6),
        }
    hi, lo, ok = jax.block_until_ready(fn(seeds))
    out["window"] = int(ok.shape[1])
    xla = jax.jit(cp._first_accepted, static_argnums=3)
    out["xla_compaction_s"] = timed(xla, hi, lo, ok, dim, calls=args.calls)[1]
    kernel = jax.jit(
        functools.partial(cp._compact_pallas, interpret=not on_chip), static_argnums=3
    )
    first, seconds = timed(kernel, hi, lo, ok, dim, calls=args.calls)
    out["kernel_first_call_s"], out["kernel_s"] = first, seconds
    out["kernel_trace"] = trace(kernel, hi, lo, ok, dim, calls=2, top=4)
    want, got = xla(hi, lo, ok, dim), kernel(hi, lo, ok, dim)
    out["kernel_equals_xla"] = bool(
        jnp.array_equal(want[0], got[0]) & jnp.array_equal(want[1], got[1])
    )
    line = json.dumps(out)
    tag = args.tag or pathlib.Path(args.tree).name
    os.makedirs(REPO / "chiprun_out", exist_ok=True)
    (REPO / "chiprun_out" / f"fold-step0-{tag}.json").write_text(line + "\n")
    print(line)
    return 0 if out["kernel_equals_xla"] and out["rounds_equal_host"] else 1


if __name__ == "__main__":
    sys.exit(main())
