#!/usr/bin/env python3
"""Set-up, stage by stage, parent against change in one cell, on the same
chip, in one call: two unpacked trees, a compile cache each; one cold run a
side (its ``setup_s`` is the ledger's ``first_setup_s``), then ``--pairs``
warm pairs, the side that goes first alternating, the two runs of a pair on
one seed; then one run a side under ``scripts/setup_events.py`` (what a warm
set-up loads and compiles, by name) and each cache's entries with their sizes.

    mkdir -p .archive_tree/parent .archive_tree/change
    git archive <parent> | tar -x -C .archive_tree/parent
    git archive $(git write-tree) | tar -x -C .archive_tree/change
    chiprun --timeout 2400 -- python scripts/chip_setup_ab.py --cell c5-masked --pairs 7

From each run's record (``benchmark/out/rounds-*.json``): ``setup_s``, every
entry of ``setup_stages_s``, ``warmup_round_s`` and the rest (``setup_s`` less
the stages: what a session builds after its input, and what warm-up does
after its round). Rows: ``chiprun_out/setup-ab-<cell>.jsonl``; the two sides'
medians side by side: ``chiprun_out/setup-ab-<cell>.json`` and the last line.
This process never imports jax: a chip belongs to the run it starts.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import statistics
import subprocess
import sys
import tempfile

REPO = pathlib.Path(__file__).resolve().parents[1]
OUT = REPO / "chiprun_out"


def run(side, tree, cache, cell, seed, seconds, phase) -> dict:
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=cache)
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed", str(seed),
         "--seconds", str(seconds), "--trace", "0"],
        cwd=tree, env=env, stdout=subprocess.PIPE, text=True,
    )
    row = {"side": side, "phase": phase, "seed": seed, "rc": done.returncode}
    record = pathlib.Path(tree) / "benchmark" / "out" / f"rounds-{cell}-seed{seed}-trace0.json"
    if done.returncode == 0 and record.exists():
        line = json.loads(done.stdout.strip().splitlines()[-1])
        rec = json.loads(record.read_text())
        stages = rec["setup_stages_s"]
        row.update(
            correct=line["correct"], round_s=rec["round_s"], setup_s=rec["setup_s"],
            warmup_round_s=rec["warmup_round_s"][0], **{f"stage.{k}": v for k, v in stages.items()},
            rest=rec["setup_s"] - sum(stages.values()),
        )
    print(json.dumps(row), file=sys.stderr, flush=True)
    with open(OUT / f"setup-ab-{cell}.jsonl", "a") as f:
        f.write(json.dumps(row) + "\n")
    return row


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default="c5-masked")
    ap.add_argument("--pairs", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--seed", type=int, default=2_600_000_360)
    ap.add_argument("--parent", default=str(REPO / ".archive_tree" / "parent"))
    ap.add_argument("--change", default=str(REPO / ".archive_tree" / "change"))
    args = ap.parse_args()
    OUT.mkdir(exist_ok=True)
    trees = {"parent": args.parent, "change": args.change}
    caches = {side: tempfile.mkdtemp(prefix=f"cache-{side}-") for side in trees}

    rows = [run(s, trees[s], caches[s], args.cell, args.seed, 5, "cold") for s in trees]
    for i in range(args.pairs):
        for side in ("parent", "change") if i % 2 == 0 else ("change", "parent"):
            rows.append(run(side, trees[side], caches[side], args.cell, args.seed + 1 + i,
                            args.seconds, "warm"))
    for side, tree in trees.items():
        subprocess.run(
            [sys.executable, str(REPO / "scripts" / "setup_events.py"), "--tree", tree,
             "--tag", f"{args.cell}-{side}", "--", "--workload", args.cell,
             "--seed", str(args.seed + 100), "--seconds", "5", "--trace", "0"],
            env=dict(os.environ, JAX_COMPILATION_CACHE_DIR=caches[side]), stdout=subprocess.DEVNULL,
        )

    summary = {"cell": args.cell, "pairs": args.pairs, "first_setup_s": {}, "warm_median": {}, "cache": {}}
    for side in trees:
        mine = [r for r in rows if r["side"] == side and "setup_s" in r]
        summary["first_setup_s"][side] = [r["setup_s"] for r in mine if r["phase"] == "cold"]
        warm = [r for r in mine if r["phase"] == "warm"]
        keys = [k for k in (warm[0] if warm else {}) if k not in ("side", "phase", "seed", "rc", "correct")]
        summary["warm_median"][side] = {k: statistics.median(r[k] for r in warm) for k in keys}
        summary["warm_median"][side]["runs"] = len(warm)
        summary["warm_median"][side]["all_correct"] = all(r["correct"] for r in warm)
        summary["warm_median"][side]["setup_s_each"] = [r["setup_s"] for r in warm]
        files = sorted(pathlib.Path(caches[side]).iterdir())
        summary["cache"][side] = {
            "entries": len(files), "bytes": sum(f.stat().st_size for f in files),
            "largest": sorted(((f.stat().st_size, f.name[:60]) for f in files), reverse=True)[:6],
        }
    (OUT / f"setup-ab-{args.cell}.json").write_text(json.dumps(summary, indent=1))
    print(json.dumps(summary))
    return 0 if all(r["rc"] == 0 for r in rows) else 1


if __name__ == "__main__":
    raise SystemExit(main())
