#!/usr/bin/env python3
"""The device reveal's aggregate reaching the host, alone: ``fetch_aggregate``
of each tree given, at a dimension (default the silo cohort's 109 482 240),
on words made fresh on the device for every call. Per call: its seconds,
the span ``fabric.reveal.fetch`` inside it (the device words' crossing), and
the rest, what the host does with the words once they are there (the join of
a low and a high word plane into int64, or the view of interleaved words).
A tree whose ``fetch_aggregate`` takes ``(lo, hi)`` gets the two planes; one
that takes ``words`` the interleaved array.

    python scripts/chip_reveal_fetch_step0.py --tree <other checkout> --tree .

One process a tree, one after the other (a chip belongs to one process); this
one never imports jax. Rows: ``chiprun_out/reveal-fetch-step0.jsonl``; the
last line has each tree's medians.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import statistics
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parents[1]
OUT = REPO / "chiprun_out"


def child(dim: int, reps: int) -> None:
    """Runs in the tree's root, with the tree first on the path."""
    import inspect
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from sda_tpu import telemetry
    from sda_tpu.parallel import epilogue

    planes = len(inspect.signature(epilogue.fetch_aggregate).parameters) == 2

    def parts(c, seed):
        lo = c * jnp.uint32(2654435761) + seed
        return lo, (lo >> jnp.uint32(4)) & jnp.uint32((1 << 29) - 1)

    @jax.jit
    def make(seed):
        if planes:
            return parts(jnp.arange(dim, dtype=jnp.uint32), seed)
        # flat, word j of coordinate j >> 1: an (N, 2) array would take 128
        # lanes a row on the chip
        j = jnp.arange(2 * dim, dtype=jnp.uint32)
        lo, hi = parts(j >> jnp.uint32(1), seed)
        return jnp.where(j & jnp.uint32(1), hi, lo)

    want_hi = None
    for rep in range(reps):
        words = jax.block_until_ready(make(jnp.uint32(rep)))
        start = time.perf_counter()
        out = epilogue.fetch_aggregate(*words) if planes else epilogue.fetch_aggregate(words)
        total = time.perf_counter() - start
        fetch = telemetry.spans("fabric.reveal.fetch")[-1]["duration_s"]
        if want_hi is None:  # one check of the bits, outside the timing
            c = (np.arange(dim, dtype=np.uint64) * 2654435761 % (1 << 32)).astype(np.uint32)
            want_hi = ((c >> 4) & ((1 << 29) - 1)).astype(np.int64)
            assert np.array_equal(out >> 32, want_hi) and np.array_equal(out & 0xFFFFFFFF, c)
        print(json.dumps({
            "rep": rep, "form": "planes" if planes else "words", "total_s": total,
            "fetch_s": fetch, "after_fetch_s": total - fetch, "dtype": str(out.dtype),
            "shape": list(out.shape), "writeable": bool(out.flags.writeable),
            "aligned": bool(out.flags.aligned), "device": jax.devices()[0].device_kind,
        }), flush=True)
        del out, words


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tree", action="append", default=[])
    ap.add_argument("--dim", type=int, default=109_482_240)
    ap.add_argument("--reps", type=int, default=6)
    ap.add_argument("--child", action="store_true")
    args = ap.parse_args()
    if args.child:
        child(args.dim, args.reps)
        return 0
    OUT.mkdir(exist_ok=True)
    summary = {}
    for tree in args.tree or [str(REPO)]:
        root = pathlib.Path(tree).resolve()
        done = subprocess.run(
            [sys.executable, str(pathlib.Path(__file__).resolve()), "--child",
             "--dim", str(args.dim), "--reps", str(args.reps)],
            cwd=root, env={**os.environ, "PYTHONPATH": str(root)},
            stdout=subprocess.PIPE, text=True,
        )
        rows = [json.loads(line) for line in done.stdout.splitlines() if line.startswith("{")]
        with open(OUT / "reveal-fetch-step0.jsonl", "a") as f:
            for row in rows:
                f.write(json.dumps({"tree": tree, **row}) + "\n")
        warm = rows[1:] or rows  # the first call also pays for the first transfer
        summary[tree] = {"rc": done.returncode}
        for key in ("total_s", "fetch_s", "after_fetch_s") if warm else ():
            summary[tree][key] = statistics.median(r[key] for r in warm)
        print(json.dumps({"tree": tree, "rows": rows}), file=sys.stderr, flush=True)
    print(json.dumps(summary))
    return 0 if all(s["rc"] == 0 for s in summary.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
