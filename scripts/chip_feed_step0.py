#!/usr/bin/env python3
"""Step 0 of the host feed, on the chip: what the host link gives the program.

    chiprun -- python scripts/chip_feed_step0.py [--rows 2500] [--dim 100000]

At ``c5-hostfed``'s shapes (a block of 2 500 x 100 000 int64, 2.0e9 B, from an
ordinary numpy array): one ``jax.device_put`` of a block timed to
``block_until_ready``, five times, whole and as its five ``(500, dim)`` row
slices in one call; two and three blocks dispatched back to back (4.0e9 B in
flight cross at the link's rate, 6.0e9 fall off the runtime's staging pool:
``sda_tpu.parallel.round.LINK_BYTES``); the chunk step alone over a landed
block; then ``FoldRound.fold_host_rows`` over four blocks at ``in_flight`` 1,
2 and 3 with its spans' seconds, and at 3 with the bound on the bytes crossing
swept, untraced and under the profiler; a chunk fetched back to the host. One
JSON line; also ``chiprun_out/feed-step0.json``.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import resource
import shutil
import sys
import tempfile
import time

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--rows", type=int, default=2500)
    parser.add_argument("--dim", type=int, default=100_000)
    parser.add_argument("--chunk", type=int, default=500)
    parser.add_argument("--blocks", type=int, default=4)
    parser.add_argument(
        "--sweep", type=int, nargs="*",
        default=[800_000_000, 1_200_000_000, 2_000_000_000, 3_200_000_000, 4_000_000_000],
        help="bounds on the bytes crossing at once to try in place of round.LINK_BYTES",
    )
    args = parser.parse_args(argv)

    import jax
    import numpy as np

    from sda_tpu import telemetry
    from sda_tpu.ops import find_packed_parameters
    from sda_tpu.ops.jaxcfg import ensure_x64
    from sda_tpu.parallel import fold_round
    from sda_tpu.parallel.sumfirst import value_limb_sums_chunk
    from sda_tpu.protocol import PackedShamirSharing

    ensure_x64()
    device = jax.devices()[0]
    out = {"device": {"platform": device.platform, "kind": device.device_kind}}
    p, w2, w3 = find_packed_parameters(5, 2, 8, min_modulus_bits=60, seed=0)
    driver = fold_round(PackedShamirSharing(5, 8, 2, p, w2, w3), args.dim, value_limb_sums_chunk, args.chunk)

    t0 = time.perf_counter()
    rng = np.random.default_rng(34)
    blocks = [
        rng.integers(0, 1 << 60, size=(args.rows, args.dim), dtype=np.int64)
        for _ in range(args.blocks)
    ]
    nbytes = blocks[0].nbytes
    out["block_bytes"] = nbytes
    out["host_blocks_made_s"] = time.perf_counter() - t0

    def slices(block):
        return [block[r : r + args.chunk] for r in range(0, args.rows, args.chunk)]

    def put(what):
        """``(seconds of the call, seconds to ready)`` of one device_put."""
        t0 = time.perf_counter()
        on_device = jax.device_put(what)
        called = time.perf_counter() - t0
        jax.block_until_ready(on_device)
        return called, time.perf_counter() - t0

    for name, make in (("whole", lambda b: b), ("slices", slices)):
        each = [put(make(blocks[i % len(blocks)])) for i in range(5)]
        out[f"put_{name}_call_s"] = [c for c, _r in each]
        out[f"put_{name}_ready_s"] = [r for _c, r in each]
        out[f"put_{name}_gb_per_s"] = [nbytes / r / 1e9 for _c, r in each]

    for name, count in (("two", 2), ("three", 3)):
        t0 = time.perf_counter()
        landed = [jax.device_put(slices(b)) for b in blocks[:count]]
        called = time.perf_counter() - t0
        jax.block_until_ready(landed)
        ready = time.perf_counter() - t0
        out[f"put_{name}_call_s"], out[f"put_{name}_ready_s"] = called, ready
        out[f"put_{name}_gb_per_s"] = count * nbytes / ready / 1e9
    landed = landed[0]

    # the chunk step alone over a landed block (the first call compiles)
    key = jax.random.key(7)
    t0 = time.perf_counter()
    jax.block_until_ready(driver.fold_chunks(landed, key))
    out["steps_first_s"] = time.perf_counter() - t0
    alone = []
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(driver.fold_chunks(landed, key))
        alone.append(time.perf_counter() - t0)
    out["steps_of_a_landed_block_s"] = alone
    del landed

    # steps dispatched behind a put that has not landed: does the host block?
    t0 = time.perf_counter()
    chunks = jax.device_put(slices(blocks[0]))
    t1 = time.perf_counter()
    acc = driver.fold_chunks(chunks, key)
    t2 = time.perf_counter()
    jax.block_until_ready(acc)
    t3 = time.perf_counter()
    del chunks, acc
    out["steps_behind_a_put_s"] = {"put_call": t1 - t0, "dispatch": t2 - t1, "wait": t3 - t2}

    def feed_once(in_flight):
        telemetry.reset()
        t0 = time.perf_counter()
        acc = driver.fold_host_rows(blocks, key, in_flight=in_flight)
        dispatched = time.perf_counter() - t0
        jax.block_until_ready(acc)
        total = time.perf_counter() - t0
        spans = telemetry.spans("fabric.feed")
        return {
            "dispatch_s": dispatched,
            "round_s": total,
            "gb_per_s": len(blocks) * nbytes / total / 1e9,
            "put_s": sum(s["duration_s"] for s in spans if s["name"] == "fabric.feed.put"),
            "wait_s": {
                on: [
                    s["duration_s"] for s in spans
                    if s["name"] == "fabric.feed.wait" and s["attrs"]["on"] == on
                ]
                for on in ("in_flight", "link")
            },
            "peak_bytes_in_use": (device.memory_stats() or {}).get("peak_bytes_in_use"),
        }

    feed = {}
    for in_flight in (1, 2, 3, 3, 3):
        feed.setdefault(str(in_flight), []).append(feed_once(in_flight))

    # how many bytes may cross at once: the feed's own bound swept, eight
    # rounds each; a round that fell off the staging pool takes seconds
    from sda_tpu.parallel import round as round_module

    stated = round_module.LINK_BYTES
    sweep = {"untraced": {}, "traced": {}}
    print(f"[step0] before the sweep: {json.dumps(out)}", file=sys.stderr, flush=True)

    def swept(name, limit, rounds):
        round_module.LINK_BYTES = limit
        sweep[name][str(limit)] = [feed_once(3)["round_s"] for _ in range(rounds)]
        # the process's peak resident set, KiB: a road that keeps host memory shows here
        peak_rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        print(f"[step0] {name} {limit}: {sweep[name][str(limit)]} max_rss_kib={peak_rss}",
              file=sys.stderr, flush=True)

    for limit in args.sweep:
        swept("untraced", limit, 8)
    # under the profiler the link itself is slower, whatever the bound, and
    # every traced round leaves about 0.9 GB of events in host memory: two rounds
    trace_dir = tempfile.mkdtemp()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level, options.host_tracer_level = 0, 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    swept("traced", stated, 2)
    jax.profiler.stop_trace()
    shutil.rmtree(trace_dir, ignore_errors=True)
    round_module.LINK_BYTES = stated
    out["link_bytes_sweep_round_s"] = sweep

    # the other way, for set-up: a (chunk, dim) device array fetched to the
    # host, one at a time and four started together
    resident = [jax.device_put(c) for c in slices(blocks[0])[:4]]
    jax.block_until_ready(resident)
    t0 = time.perf_counter()
    np.asarray(resident[0])
    out["fetch_one_chunk_s"] = time.perf_counter() - t0
    resident = [jax.device_put(c) for c in slices(blocks[1])[:4]]
    jax.block_until_ready(resident)
    t0 = time.perf_counter()
    for chunk in resident:
        chunk.copy_to_host_async()
    for chunk in resident:
        np.asarray(chunk)
    out["fetch_four_chunks_started_together_s"] = time.perf_counter() - t0
    del resident
    out["feed"] = feed
    out["memory_stats"] = device.memory_stats()

    line = json.dumps(out)
    directory = REPO / "chiprun_out"
    directory.mkdir(exist_ok=True)
    (directory / "feed-step0.json").write_text(line)
    print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
