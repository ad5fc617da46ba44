#!/usr/bin/env python3
"""One run of ``benchmark/run.py`` with the feed's own bound on the bytes that
cross the host link at once (``sda_tpu.parallel.round.LINK_BYTES``) set to
another value, to read what the bound costs a cell: in this process the
constant is replaced before the tree's ``run.py`` runs as ``__main__``.

    chiprun -- python scripts/chip_link_bound.py --link-bytes 800000000 -- \
        --workload c5-hostfed-masked --seed 7 --seconds 30 --trace 0

The run's own line goes to stdout, tagged with the bound, and is appended to
``chiprun_out/link-bound.jsonl`` with the run's median spans (``dispatch``,
``fold``, ``unmask``). PR 39 read ``c5-hostfed-masked`` this way (PERF.md
section 6): chunks put together share the link, so the more may cross at once,
the later the first of them is whole on the chip and the later the fold starts.
"""

import argparse
import io
import json
import pathlib
import runpy
import statistics
import sys
from contextlib import redirect_stdout

REPO = pathlib.Path(__file__).resolve().parents[1]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--link-bytes", type=int, required=True)
    ap.add_argument("rest", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    rest = args.rest[1:] if args.rest[:1] == ["--"] else args.rest
    said = dict(zip(rest[::2], rest[1::2]))

    sys.path.insert(0, str(REPO))
    from sda_tpu.parallel import round as round_module

    round_module.LINK_BYTES = args.link_bytes
    sys.argv = [str(REPO / "benchmark" / "run.py"), *rest]
    printed = io.StringIO()
    try:
        with redirect_stdout(printed):
            runpy.run_path(sys.argv[0], run_name="__main__")
        rc = 0
    except SystemExit as e:
        rc = e.code if isinstance(e.code, int) else int(bool(e.code))
    if rc:
        return rc
    row = {"link_bytes": args.link_bytes, "line": json.loads(printed.getvalue().splitlines()[-1])}
    record = "benchmark/out/rounds-{--workload}-seed{--seed}-trace{--trace}.json".format(**said)
    spans = json.loads((REPO / record).read_text())["spans"]
    row["spans_median_s"] = {name: statistics.median(took) for name, took in spans.items()}
    (REPO / "chiprun_out").mkdir(exist_ok=True)
    with open(REPO / "chiprun_out" / "link-bound.jsonl", "a") as f:
        f.write(json.dumps(row) + "\n")
    print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
