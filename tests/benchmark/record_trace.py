"""How ``recorded-trace-x4.json`` was made (on the chip, PR 23):

    chiprun --chips 4 -- python tests/benchmark/record_trace.py chiprun_out/recorded-trace-x4.json

A tiny twin of the four-chip cell is run traced on the TPU chips through the
harness; the profiler's trace is read into the plain structure
``benchmark/trace_reduce.py`` reduces, cut to the first rounds, and written
with the numbers the reduction gave on the day, so that the test can hold the
reduction to them.
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
    from benchmark import harness, trace_reduce

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < 4:
        print("needs four TPU chips", file=sys.stderr)
        return 2
    with tempfile.TemporaryDirectory() as tmp:
        root = bench_tree.tiny_tree(pathlib.Path(tmp) / "copy")
        cell = "tiny-c5-sumfirst-x4"
        names = harness.span_names(harness.load_cell(root, cell))
        line = harness.run_cell(
            root, cell, 1, 0.05, True, devices, time.perf_counter(), out_dir=root / "out",
            keep_trace=True,
        )
        trace = sorted((root / "out").rglob("*.xplane.pb"))[-1]
        raw = trace_reduce.load_xplane(trace, names)
    rounds = sorted(
        (s, s + d)
        for plane in raw["planes"] for line_ in plane["lines"]
        for n, s, d in line_["events"] if n == "round"
    )[:ROUNDS_KEPT]
    start, end = rounds[0][0] - 1e5, rounds[-1][1] + 1e5
    for plane in raw["planes"]:
        for line_ in plane["lines"]:
            line_["events"] = [
                e for e in line_["events"] if e[1] >= start and e[1] + e[2] <= end
            ]
    reduced = trace_reduce.reduce(raw, names)
    total, exposed = reduced.collective_seconds()
    raw["recorded"] = {
        "device": line["device"]["kind"],
        "jax": jax.__version__,
        "rounds": len(rounds),
        "window_s": reduced.window_seconds,
        "busy_s_by_chip": {str(c): reduced.busy_seconds(c) for c in reduced.chips},
        "idle_share": reduced.idle_share(),
        "collective_s": total,
        "collective_exposed_s": exposed,
        "top_operations": reduced.top_operations(5),
        "idle_gaps": reduced.idle_gaps_by_span(10),
    }
    pathlib.Path(target).parent.mkdir(parents=True, exist_ok=True)
    pathlib.Path(target).write_text(json.dumps(raw))
    print(json.dumps(raw["recorded"], indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
