"""CLI acceptance: run the walkthrough script end-to-end over a real server
process and assert the documented expected output (reference:
docs/simple-cli-example.sh, README.md:157)."""

import os
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent


def test_simple_cli_example():
    env = dict(os.environ)
    env["SDA_PORT"] = "18871"
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        ["sh", str(REPO / "scripts" / "simple-cli-example.sh")],
        capture_output=True,
        text=True,
        timeout=300,
        env=env,
    )
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "result: 0 2 2 4 4 6 6 8 8 10" in proc.stdout, proc.stdout


def _cpu_env():
    """Explicit-CPU env for the script children (conftest already pinned
    JAX_PLATFORMS=cpu and switched the persistent compile cache off)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # an ambient fault plan must not change which code path a child runs
    env.pop("SDA_FAULTS", None)
    # test subprocesses must not litter bench-artifacts/
    env["SDA_BENCH_ARTIFACTS"] = "0"
    return env


def test_bench_takes_no_argument_and_needs_no_jax():
    """``bench.py`` is the host riders' runner: any argument is a usage
    error (exit 2, a usage line on stderr that points at the chip's
    benchmark, no line of its own on stdout), and JAX is not imported on
    the way there."""
    code = (
        "import runpy, sys\n"
        "sys.argv = ['bench.py', '--anything']\n"
        "try:\n"
        "    runpy.run_path('bench.py', run_name='__main__')\n"
        "except SystemExit as exit:\n"
        "    rc = exit.code\n"
        "print('rc', rc, 'jax', 'jax' in sys.modules)\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, env=_cpu_env(), cwd=REPO, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-1500:]
    assert out.stdout.strip() == "rc 2 jax False"
    assert out.stderr.startswith("usage: python bench.py")
    assert "benchmark/run.py --workload" in out.stderr


def test_rest_ingest_script_sqlite():
    """scripts/rest_ingest.py (the sustained REST+sqlite ingest
    measurement, VERDICT r4 #6) at a small n: the transcript setup
    replays, every POST is accepted, the stored row count is re-verified
    through the store, and the artifact carries the measured rate."""
    import json

    env = _cpu_env()
    out = subprocess.run(
        [
            sys.executable, str(REPO / "scripts" / "rest_ingest.py"),
            "--n", "300", "--threads", "3", "--backend", "sqlite",
        ],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=240,
    )
    assert out.returncode == 0, out.stderr[-1500:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["backend"] == "sqlite" and line["n"] == 300
    assert line["stored_rows_verified"] is True
    assert line["participations_per_s"] > 0
    assert sum(w["ok"] for w in line["per_worker"]) == 300
