#!/usr/bin/env python3
"""Where one run of ``benchmark/run.py`` spends its set-up and its window, by
the program's own timeline: the tree's ``run.py`` runs as ``__main__`` in this
process; afterwards the program's span ring and counters (``telemetry.snapshot``:
JAX's trace / lower / compile / load events by program name, the fabric's host
spans) are cut by the run's own record into set-up and window. The compiler's
log adds what no event says: every program that asked the persistent compile
cache, in order, with ``hit`` or ``miss`` and its key.

    python scripts/setup_events.py [--tree <checkout>] [--tag <t>] -- \
        --workload c5-masked --seed 7 --seconds 5 --trace 0

Writes ``chiprun_out/timeline-<tag>.json`` (the snapshot, ``programs``, ``run``
and the two ``reports`` with their intervals; ``scripts/trace_report.py`` reads
it), prints the reports on stderr, lets the run's own line through on stdout.
A tree from before the program kept a timeline gets ``programs`` alone.
"""

import argparse
import json
import logging
import os
import pathlib
import runpy
import sys
import time

REPO = pathlib.Path(__file__).resolve().parents[1]


class _Programs(logging.Handler):
    """The compiler's "cache hit for <name>" / "CACHE MISS for <name>" lines."""

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.rows = []

    def emit(self, record):
        message = record.getMessage()
        for mark, kind in (("cache hit for", "hit"), ("CACHE MISS for", "miss")):
            if mark in message:
                name, _, key = message.split(mark, 1)[1].partition(" with key ")
                self.rows.append([name.strip(" '"), kind, key.strip(" '")])


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tree", default=str(REPO))
    ap.add_argument("--tag", default=None)
    ap.add_argument("rest", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    tree = pathlib.Path(args.tree).resolve()
    rest = args.rest[1:] if args.rest[:1] == ["--"] else args.rest
    said = dict(zip(rest[::2], rest[1::2]))

    programs = _Programs()
    compiler_log = logging.getLogger("jax._src.compiler")
    compiler_log.setLevel(logging.DEBUG)
    compiler_log.addHandler(programs)
    compiler_log.propagate = False

    os.chdir(tree)
    sys.argv = [str(tree / "benchmark" / "run.py"), *rest]
    started = time.perf_counter()
    try:
        runpy.run_path(sys.argv[0], run_name="__main__")
        rc = 0
    except SystemExit as e:
        rc = e.code if isinstance(e.code, int) else int(bool(e.code))

    out = {"programs": programs.rows, "run": {"tree": str(tree), "argv": rest, "rc": rc}}
    if rc == 0:
        from sda_tpu.telemetry import flight  # the tree's own program, as run.py imported it
    if rc == 0 and hasattr(flight, "interval_report"):
        import sda_tpu.telemetry as telemetry
        from trace_report import print_interval  # beside this script

        record = "benchmark/out/rounds-{--workload}-seed{--seed}-trace{--trace}.json"
        rec = json.loads((tree / record.format(**said)).read_text())
        first = started + rec["setup_s"]
        each = zip(rec["round_start_s_each"], rec["round_s_each"])
        rounds = [[first + at, first + at + took] for at, took in each]
        out.update(telemetry.snapshot(telemetry.RING_RECORDS))
        out["reports"] = {
            "setup": flight.interval_report(out["spans"], started, first),
            "window": flight.interval_report(
                out["spans"], first, first + rec["window_s"], rounds=rounds
            ),
        }
        for title, report in out["reports"].items():
            print_interval(f"{said['--workload']} {title}", report, file=sys.stderr)
    os.makedirs(REPO / "chiprun_out", exist_ok=True)
    dump = REPO / "chiprun_out" / f"timeline-{args.tag or tree.name}.json"
    dump.write_text(json.dumps(out, indent=1))
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
