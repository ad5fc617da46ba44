"""A temporary copy of the benchmark with tiny cells dropped in as new
files: what a later PR does when it adds a configuration, a traffic mix, a
layer metric or a cell, and how the tests run the rounds on the CPU."""

from __future__ import annotations

import json
import pathlib
import shutil

REPO = pathlib.Path(__file__).resolve().parents[2]

#: tiny twins of the four cells: (cell, config it is cut from, traffic it is
#: cut from, dim, rows, passes, chunk, mesh)
TINY_CELLS = [
    ("tiny-c5-sumfirst", "c5-w61-d100k", "sumfirst-wide", 62, 24, 1, 6, None),
    ("tiny-c5-sumfirst-x4", "c5-w61-d100k", "sumfirst-wide-x4", 62, 32, 1, 8, {"p": 4, "d": 1}),
    ("tiny-c4-participant", "c4-w31-d50k", "participant-narrow", 33, 24, 1, 6, None),
    ("tiny-c4-sumfirst", "c4-w31-d50k", "sumfirst-narrow", 33, 12, 2, 6, None),
]


def copy_benchmark(dest: pathlib.Path) -> pathlib.Path:
    """``BENCHMARK.json`` and ``benchmark/`` (run outputs left behind) under
    ``dest``."""
    dest.mkdir(parents=True, exist_ok=True)
    shutil.copy(REPO / "BENCHMARK.json", dest / "BENCHMARK.json")
    shutil.copytree(
        REPO / "benchmark", dest / "benchmark",
        ignore=shutil.ignore_patterns("out", "__pycache__"),
    )
    return dest


def add_cell(root, name, config_from, traffic_from, dim, rows, passes, chunk, mesh,
             **traffic_changes):
    """Add one tiny cell to the copy: a new configuration file, a new
    traffic file and one new ``workloads`` entry. Edits no file that was
    there, but the manifest, which gains entries."""
    root = pathlib.Path(root)
    config = json.loads((root / "benchmark/configs" / f"{config_from}.json").read_text())
    config.update(name=f"{name}-config", dim=dim)
    config_file = f"benchmark/configs/{name}-config.json"
    (root / config_file).write_text(json.dumps(config))
    traffic = json.loads((root / "benchmark/traffic" / f"{traffic_from}.json").read_text())
    traffic.update(name=f"{name}-traffic", rows=rows, passes=passes, chunk=chunk, mesh=mesh)
    traffic.update(traffic_changes)
    (root / "benchmark/traffic" / f"{name}-traffic.json").write_text(json.dumps(traffic))
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": f"{name}-config", "source": config["source"], "file": config_file,
        "reduced": ["participants", "dim"], "why": "a tiny twin for the CPU tests",
    })
    manifest["workloads"].append({
        "name": name, "config": f"{name}-config", "traffic": f"{name}-traffic",
        "chips": 4 if mesh else 1, "why": "a tiny twin for the CPU tests",
    })
    (root / "BENCHMARK.json").write_text(json.dumps(manifest, indent=1))
    return name


def tiny_tree(dest: pathlib.Path) -> pathlib.Path:
    root = copy_benchmark(dest)
    for cell in TINY_CELLS:
        add_cell(root, *cell)
    return root
