"""100K-cohort orchestration stress (SURVEY hard part #6, VERDICT r1 #5).

The server-side transpose is the scalability-critical piece: the
reference's jfs path materializes every ciphertext at once
(server/src/stores.rs:86-101) while its mongo path spills to disk
(aggregations.rs:182-186). Our sqlite and file backends stream one clerk
column at a time — these tests push a >= 100K-participation cohort
through both and assert peak RSS growth stays bounded by ~one column,
not the full matrix. Each run is a subprocess so the measurement isn't
polluted by the test process's JAX arenas.

``SDA_STRESS_N`` scales the cohort (default 100_000).
"""

import json
import os
import pathlib
import subprocess
import sys

import pytest

N = int(os.environ.get("SDA_STRESS_N", 100_000))


def _run(backend: str, tmp_path) -> dict:
    repo = pathlib.Path(__file__).resolve().parent.parent
    env = dict(os.environ, PYTHONPATH=str(repo))
    out = subprocess.run(
        [
            sys.executable,
            str(repo / "tests" / "scale_stress_worker.py"),
            backend, str(N), "8", str(tmp_path),
        ],
        capture_output=True, text=True, env=env, cwd=repo, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["delta_mb"] < line["bound_mb"], line
    return line


@pytest.mark.parametrize("backend", ["sqlite", "file"])
def test_transpose_100k_memory_flat(backend, tmp_path):
    stats = _run(backend, tmp_path)
    sys.stderr.write(f"\n[stress {backend}] {stats}\n")
