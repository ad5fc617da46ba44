"""How ``recorded-trace-scopes.json`` was made (on the chip, PR 24):

    chiprun --chips 4 -- python tests/benchmark/record_scopes_trace.py chiprun_out/recorded-trace-scopes.json

The tiny twin of the four-chip cell is run traced on the TPU chips through
the harness; the trace (``trace_reduce``'s plain structure, the
program's ``fabric.*`` host spans kept) is cut to its first rounds and
written with the join table of the operations it holds and with the report
the reader gave on the day, so that the test can hold the reader to it.
"""

import json
import pathlib
import sys
import tempfile
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1]), str(HERE)]

ROUNDS_KEPT = 3


def main(target: str) -> int:
    import jax

    import bench_tree
    from benchmark import harness, scopes

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < 4:
        print("needs four TPU chips", file=sys.stderr)
        return 2
    with tempfile.TemporaryDirectory() as tmp:
        root = bench_tree.tiny_tree(pathlib.Path(tmp) / "copy")
        cell = harness.load_cell(root, "tiny-c5-sumfirst-x4")
        names = harness.span_names(cell)
        line = harness.run_cell(
            root, cell.name, 1, 0.05, True, devices, time.perf_counter(), out_dir=root / "out",
            log=lambda message: None, keep_trace=True,
        )
        raw = scopes.load(sorted((root / "out").rglob("*.xplane.pb"))[-1], names)
        paths = scopes.join_table(harness.round_of(cell).steps(cell, devices))
    rounds = sorted(
        (s, s + d)
        for plane in raw["planes"] for line_ in plane["lines"]
        for n, s, d in line_["events"] if n == "round"
    )[:ROUNDS_KEPT]
    start, end = rounds[0][0] - 1e5, rounds[-1][1] + 1e5
    held = set()
    for plane in raw["planes"]:
        for line_ in plane["lines"]:
            line_["events"] = [
                e for e in line_["events"] if e[1] >= start and e[1] + e[2] <= end
            ]
            held.update(e[0] for e in line_["events"])
    # the join table, cut to the operations the trace holds
    raw["paths"] = {k: v for k, v in paths.items() if k.split("/", 1)[1] in held}
    raw["recorded"] = {
        "device": line["device"]["kind"],
        "jax": jax.__version__,
        **scopes.split(raw, raw["paths"], names),
    }
    pathlib.Path(target).parent.mkdir(parents=True, exist_ok=True)
    pathlib.Path(target).write_text(json.dumps(raw))
    print(json.dumps(raw["recorded"], indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
