#!/usr/bin/env python3
"""What one run of ``benchmark/run.py`` traces, lowers, compiles and loads,
by JAX's own monitoring events and its compiler's log: every event's count
and seconds by name, and every program that asked the persistent compile
cache, in order, with ``hit`` or ``miss`` and its key. The harness is not
edited: the listeners are registered here, then the tree's ``run.py`` runs as
``__main__`` in this process.

    python scripts/setup_events.py [--tree <checkout>] [--tag <t>] -- \
        --workload c5-masked --seed 7 --seconds 5 --trace 0

An untraced run compiles nothing in or after its window (``compiles_in_window``
is held to 0), so what is counted is set-up's. Importing jax before ``run.py``
moves the import out of ``to_harness``: read counts and programs here, and
seconds from plain runs. Writes ``chiprun_out/setup-events-<tag>.json``; the
run's own line passes through on stdout.
"""

from __future__ import annotations

import argparse
import collections
import json
import logging
import os
import pathlib
import runpy
import sys

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

    import jax.monitoring

    events = collections.defaultdict(lambda: [0, 0.0])

    def on_duration(name, seconds, **_kw):
        events[name][0] += 1
        events[name][1] += seconds

    def on_event(name, **_kw):
        events[name][0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    programs = _Programs()
    compiler_log = logging.getLogger("jax._src.compiler")
    compiler_log.setLevel(logging.DEBUG)
    compiler_log.addHandler(programs)
    compiler_log.propagate = False

    os.chdir(tree)
    sys.argv = [str(tree / "benchmark" / "run.py"), *rest]
    try:
        runpy.run_path(sys.argv[0], run_name="__main__")
        rc = 0
    except SystemExit as e:
        rc = e.code if isinstance(e.code, int) else int(bool(e.code))

    out = {
        "tree": str(tree),
        "argv": rest,
        "rc": rc,
        "events": {k: {"count": c, "seconds": s} for k, (c, s) in sorted(events.items())},
        "programs": programs.rows,
        "hits": sum(kind == "hit" for _n, kind, _k in programs.rows),
        "misses": sum(kind == "miss" for _n, kind, _k in programs.rows),
    }
    os.makedirs(REPO / "chiprun_out", exist_ok=True)
    tag = args.tag or tree.name
    (REPO / "chiprun_out" / f"setup-events-{tag}.json").write_text(json.dumps(out, indent=1))
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
