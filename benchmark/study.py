"""The spread study: several sets of runs of one cell, every round's seconds
kept, and the table that says which noise it is.

    python benchmark/study.py run --workload <cell> --sets A B C --runs 3 \
        --seconds 40 --out chiprun_out/study
    python benchmark/study.py collect chiprun_out/study

``run`` starts each run as a process of its own (this one never touches JAX,
so the child gets the chips), one after another, each with another seed, and
keeps each run's result line beside its per-round record. ``collect`` merges
what it finds into ``benchmark/out/spread-<cell>.json`` and prints, per cell
and window: each set's median and spread (distance between the quartiles over
the median, as the driver takes it) and range (largest less smallest, over
the median) per end-to-end metric, the scatter of rounds inside a run, and the
shift of the median between runs. At the whole window it also says where a
shift lives: each run's median round split by the round's spans (the run's
record holds every span of every round), and by span the range of those
medians over the runs. Windows shorter than the one run are cut from the kept
rounds: the rounds that had started before the shorter window would have
closed.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark.harness import spread  # noqa: E402  (imports no JAX)

METRICS = ("round_s", "elems_per_s", "setup_s")


def run_sets(workload, sets, runs, seconds, out, first_seed) -> int:
    out = pathlib.Path(out) / workload
    out.mkdir(parents=True, exist_ok=True)
    seed = first_seed
    for label in sets:
        for _ in range(runs):
            seed += 1
            done = subprocess.run(
                [sys.executable, str(ROOT / "benchmark" / "run.py"), "--workload", workload,
                 "--seed", str(seed), "--seconds", str(seconds), "--trace", "0"],
                cwd=ROOT, capture_output=True, text=True,
            )
            lines = done.stdout.strip().splitlines()
            if done.returncode or not lines:
                print(done.stderr[-3000:], file=sys.stderr)
                print(f"{workload} set {label} seed {seed}: exit {done.returncode}")
                return done.returncode or 1
            line = json.loads(lines[-1])
            rounds = ROOT / "benchmark" / "out" / f"rounds-{workload}-seed{seed}-trace0.json"
            record = json.loads(rounds.read_text())
            record.update(set=label, line=line)
            (out / f"{label}-seed{seed}.json").write_text(json.dumps(record))
            print(
                f"{workload} set {label} seed {seed}: correct={line['correct']} "
                + " ".join(f"{k}={v['value']:.6g}" for k, v in line["metrics"].items()),
                flush=True,
            )
    return 0


def cut(record, window):
    """The run's metrics had its window been ``window`` seconds: over the
    rounds that had started by then."""
    starts = record["round_start_s_each"]
    kept = [s for s, start in zip(record["round_s_each"], starts) if start < window]
    clock = starts[len(kept) - 1] + kept[-1]  # first round's start to last round's end
    elements = record["elems_per_s"] * record["window_s"] / len(starts)  # a round's
    return {
        "round_s": statistics.median(kept),
        "elems_per_s": len(kept) * elements / clock,
        "setup_s": record["setup_s"],
        "rounds": len(kept),
        "round_spread": spread(kept),
    }


def value_range(values) -> float:
    """Largest less smallest, over the median."""
    return (max(values) - min(values)) / statistics.median(values)


def span_shift(records) -> dict:
    """Where a shift of ``round_s`` between runs lives: by span, the median
    over the runs of a run's median seconds in it, and the range of those
    medians over all runs and over the runs of the set where it is widest."""
    by_span = {}
    for record in records:
        for name, seconds in record.get("spans", {}).items():
            if seconds:
                by_span.setdefault(name, {}).setdefault(record["set"], []).append(
                    statistics.median(seconds)
                )
    return {
        name: {
            "median_s": statistics.median(v for runs in sets.values() for v in runs),
            "range_s": max(max(runs) for runs in sets.values())
            - min(min(runs) for runs in sets.values()),
            "widest_set_range_s": max(max(runs) - min(runs) for runs in sets.values()),
        }
        for name, sets in by_span.items()
    }


def collect(directory, windows) -> int:
    directory = pathlib.Path(directory)
    for cell_dir in sorted(p for p in directory.iterdir() if p.is_dir()):
        records = [json.loads(p.read_text()) for p in sorted(cell_dir.glob("*.json"))]
        if not records:
            continue
        ran = records[0]["seconds"]
        study = {
            "workload": cell_dir.name,
            "run_seconds": ran,
            "device": records[0]["device"],
            "runs": [
                {k: r[k] for k in ("set", "seed", "round_s", "elems_per_s", "setup_s",
                                   "window_s", "round_spread", "warmup_round_s",
                                   "round_s_each", "round_start_s_each")}
                | {"span_median_s": {n: statistics.median(v)
                                     for n, v in r.get("spans", {}).items() if v}}
                | {"correct": r["line"]["correct"], "failed": r["line"]["failed"],
                   "memory_peak_bytes": r["line"]["device"]["memory_peak_bytes"]}
                for r in records
            ],
            "windows": {},
        }
        print(f"\n{cell_dir.name}: {len(records)} runs of {ran:g} s")
        for window in [w for w in windows if w < ran] + [ran]:
            by_set = {}
            for r in records:
                by_set.setdefault(r["set"], []).append(cut(r, window))
            row = {"sets": {}, "rounds": statistics.median(
                c["rounds"] for cuts in by_set.values() for c in cuts)}
            for label, cuts in by_set.items():
                row["sets"][label] = {
                    m: {"median": statistics.median(c[m] for c in cuts),
                        "spread": spread([c[m] for c in cuts]),
                        "range": value_range([c[m] for c in cuts])}
                    for m in METRICS
                }
            every = [c for cuts in by_set.values() for c in cuts]
            row["inside_run_scatter"] = statistics.median(c["round_spread"] for c in every)
            row["between_run_shift"] = {m: spread([c[m] for c in every]) for m in METRICS}
            row["widest_set_spread"] = {
                m: max(s[m]["spread"] for s in row["sets"].values()) for m in METRICS
            }
            row["widest_set_range"] = {
                m: max(s[m]["range"] for s in row["sets"].values()) for m in METRICS
            }
            medians = {m: [s[m]["median"] for s in row["sets"].values()] for m in METRICS}
            row["set_medians_apart"] = {
                m: (max(v) - min(v)) / statistics.median(v) for m, v in medians.items()
            }
            study["windows"][f"{window:g}"] = row
            print(
                f"  window {window:>4g} s, {row['rounds']:g} rounds: inside-run scatter "
                f"{100 * row['inside_run_scatter']:.3f}% | "
                + " | ".join(
                    f"{m}: widest set {100 * row['widest_set_spread'][m]:.3f}% (range "
                    f"{100 * row['widest_set_range'][m]:.3f}%), all runs "
                    f"{100 * row['between_run_shift'][m]:.3f}%, sets apart "
                    f"{100 * row['set_medians_apart'][m]:.3f}%"
                    for m in METRICS
                )
            )
        study["span_shift"] = span_shift(records)
        for name, shift in study["span_shift"].items():
            print(
                f"  span {name:>9}: median {shift['median_s']:.5f} s, its runs' medians range "
                f"over {shift['range_s']:.5f} s ({shift['widest_set_range_s']:.5f} in the "
                f"widest set)"
            )
        target = ROOT / "benchmark" / "out" / f"spread-{cell_dir.name}.json"
        target.write_text(json.dumps(study, indent=1))
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    commands = parser.add_subparsers(dest="command", required=True)
    run = commands.add_parser("run")
    run.add_argument("--workload", required=True)
    run.add_argument("--sets", nargs="+", default=["A", "B", "C"])
    run.add_argument("--runs", type=int, default=3)
    run.add_argument("--seconds", type=float, required=True)
    run.add_argument("--out", default="chiprun_out/study")
    run.add_argument("--first-seed", type=int, default=100)
    gather = commands.add_parser("collect")
    gather.add_argument("directory")
    gather.add_argument("--windows", type=float, nargs="*", default=[10, 20, 30])
    args = parser.parse_args(argv)
    if args.command == "run":
        return run_sets(args.workload, args.sets, args.runs, args.seconds, args.out,
                        args.first_seed)
    return collect(args.directory, args.windows)


if __name__ == "__main__":
    sys.exit(main())
