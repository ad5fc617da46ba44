"""Run by ``test_benchmark_timeline.py`` in a process of its own, so that the
first cell's set-up is the first time the process traces, lowers and compiles
anything: each cell named on the command line, traced, through the harness on
the CPU, the program's timeline emptied before each. Prints one JSON line a
cell: the result line, the run's ``setup_s`` and the program's ``fabric.feed*``
records with the harness's ``round`` spans, for the sums the test checks.

    python run_timeline_cells.py <root of the benchmark's copy> <cell> [<cell> ...]
"""

import json
import pathlib
import sys
import time


def main(root, *cells) -> int:
    import jax

    from benchmark import harness
    from sda_tpu import telemetry
    from sda_tpu.ops.jaxcfg import ensure_x64

    ensure_x64()  # as run.py does, before anything is traced
    root = pathlib.Path(root)
    for cell in cells:
        telemetry.reset()
        started = time.perf_counter()
        line = harness.run_cell(
            root, cell, 5, 0.3, True, jax.devices("cpu"), started,
            out_dir=root / "out", log=lambda message: None,
        )
        record = json.loads((root / "out" / f"rounds-{cell}-seed5-trace1.json").read_text())
        first = started + record["setup_s"]
        print(json.dumps({
            "cell": cell, "line": line, "setup_s": record["setup_s"],
            "rounds": [[first + at, first + at + took] for at, took in
                       zip(record["round_start_s_each"], record["round_s_each"])],
            "feed": telemetry.spans(name="fabric.feed", since_mono=first),
            "jax_before_window": telemetry.spans(name="jax.", until_mono=first),
            "dropped": [c for c in telemetry.snapshot(0)["counters"]
                        if c["name"] == "sda_telemetry_spans_dropped_total"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
