"""The benchmark's one command.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process acquires the cell's chips in-process, sets up, measures for
``--seconds`` and prints one last line: the JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` in a
traced run). No TPU, or fewer chips than the cell asks for: exit code 2 and
no result line, never the CPU. Per-round times go to ``benchmark/out/``.
"""

import time

PROCESS_START = time.perf_counter()

import argparse
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
EXIT_NO_DEVICE = 2

# the TPU's library otherwise logs under /tmp/tpu_logs, a fixed path outside
# the checkout that two sides of a comparison would share
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def log(message: str) -> None:
    print(message, file=sys.stderr, flush=True)


def acquire_chips(chips: int):
    """The TPU chips of this machine, or exit: a measurement comes from a
    TPU or not at all."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        log(
            f"[benchmark] need {chips} TPU chip(s); JAX found {len(devices)} "
            f"device(s) of platform {devices[0].platform!r}. No result."
        )
        raise SystemExit(EXIT_NO_DEVICE)
    return devices


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = parser.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    try:
        import sda_tpu  # noqa: F401  the system under test
    except ImportError as e:
        log(f"[benchmark] the program is not in this checkout ({e}). No result.")
        return EXIT_NO_DEVICE
    from benchmark import harness

    cell = harness.load_cell(ROOT, args.workload)
    devices = acquire_chips(cell.chips)

    import jax

    # the program places the compile cache ($JAX_COMPILATION_CACHE_DIR or
    # <checkout>/.jax_cache); the benchmark only lets small programs in too
    from sda_tpu.ops.jaxcfg import ensure_x64

    ensure_x64()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    result = harness.run_cell(
        ROOT, args.workload, args.seed, args.seconds, bool(args.trace),
        devices, PROCESS_START, log=log,
    )
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
