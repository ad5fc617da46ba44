"""A temporary copy of the benchmark with tiny cells dropped in as new
files: what a later PR does when it adds a configuration, a traffic mix, a
layer metric or a cell, and how the tests run the rounds on the CPU."""

from __future__ import annotations

import contextlib
import hashlib
import json
import pathlib
import shutil
import sys

REPO = pathlib.Path(__file__).resolve().parents[2]

#: tiny twins of the four cells: (cell, config it is cut from, traffic it is
#: cut from, dim, rows, passes, chunk, mesh)
TINY_CELLS = [
    ("tiny-c5-sumfirst", "c5-w61-d100k", "sumfirst-wide", 62, 24, 1, 6, None),
    ("tiny-c5-sumfirst-x4", "c5-w61-d100k", "sumfirst-wide-x4", 62, 32, 1, 8, {"p": 4, "d": 1}),
    ("tiny-c4-participant", "c4-w31-d50k", "participant-narrow", 33, 24, 1, 6, None),
    ("tiny-c4-sumfirst", "c4-w31-d50k", "sumfirst-narrow", 33, 12, 2, 6, None),
]


#: the directories of the benchmark's own, ``BENCHMARK.json``'s ``paths``
PATHS = ("benchmark", "tests/benchmark")


def copy_benchmark(dest: pathlib.Path, paths=PATHS[:1]) -> pathlib.Path:
    """``BENCHMARK.json`` and ``benchmark/`` (run outputs left behind) under
    ``dest``; with ``paths=PATHS`` the benchmark's tests too."""
    dest.mkdir(parents=True, exist_ok=True)
    shutil.copy(REPO / "BENCHMARK.json", dest / "BENCHMARK.json")
    for path in paths:
        shutil.copytree(
            REPO / path, dest / path, ignore=shutil.ignore_patterns("out", "__pycache__")
        )
    return dest


def digests(root, paths=PATHS) -> dict:
    """The digest of every file of the benchmark under ``root``, by its path
    (run outputs and caches left out)."""
    root = pathlib.Path(root)
    return {
        p.relative_to(root).as_posix(): hashlib.sha256(p.read_bytes()).hexdigest()
        for path in paths
        for p in (root / path).rglob("*")
        if p.is_file() and "out" not in p.parts and "__pycache__" not in p.parts
    }


def add_cell(root, name, config_from, traffic_from, dim, rows, passes, chunk, mesh,
             **traffic_changes):
    """Add one tiny cell to the copy: a new configuration file, a new
    traffic file and one new ``workloads`` entry. Edits no file that was
    there, but the manifest, which gains entries."""
    root = pathlib.Path(root)
    config = json.loads((root / "benchmark/configs" / f"{config_from}.json").read_text())
    config.update(name=f"{name}-config", dim=dim, chunk=chunk, reduced=["participants", "dim"])
    config_file = f"benchmark/configs/{name}-config.json"
    (root / config_file).write_text(json.dumps(config))
    traffic = json.loads((root / "benchmark/traffic" / f"{traffic_from}.json").read_text())
    traffic.update(name=f"{name}-traffic", rows=rows, passes=passes, chunk=chunk, mesh=mesh)
    traffic.update(traffic_changes)
    (root / "benchmark/traffic" / f"{name}-traffic.json").write_text(json.dumps(traffic))
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": f"{name}-config", "source": config["source"], "file": config_file,
        "reduced": config["reduced"], "why": "a tiny twin for the CPU tests",
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


#: the set-up metrics (``benchmark/layers/setup_*.py``): they read the
#: program's records of what JAX traced, lowered, compiled or loaded since the
#: run began, and in a test's process a run may find every program made by an
#: earlier test, and have none to read
SETUP_METRICS = frozenset({"setup.trace_s", "setup.lower_s", "setup.load_s", "setup.compile_s"})


def reported(line) -> set:
    """The metrics a traced CPU run's line reports, the set-up metrics left
    out: those are all there or none, and none is under 0."""
    names = set(line["metrics"])
    assert names & SETUP_METRICS in (set(), SETUP_METRICS), names
    assert all(line["metrics"][name]["value"] >= 0 for name in names & SETUP_METRICS)
    return names - SETUP_METRICS


#: a metric with a list of cells whose code the toy cell shares with them
TOY_SHARED_METRIC = "epilogue.recombine_s"


def add_toy_cell(root, name, round_name, like=TINY_CELLS[0][1:], source=None):
    """What the next configuration's PR does, with the toy round
    (``toy_round.py``, or ``source``) for the masked round: the round dropped
    in as ``benchmark/rounds/<round_name>.py``, a configuration that states
    its scheme kind, a traffic file that names it, one ``workloads`` entry, a
    layer file of its own with its manifest entry, and the cell's name added
    to the ``workloads`` of one metric whose code it shares. No file that was
    there is edited but the manifest, which gains entries."""
    root = pathlib.Path(root)
    here = pathlib.Path(__file__).parent
    source = source or (here / "toy_round.py").read_text()
    (root / "benchmark/rounds" / f"{round_name}.py").write_text(source)
    add_cell(root, name, *like, round=f"benchmark.rounds.{round_name}")
    config_file = root / "benchmark/configs" / f"{name}-config.json"
    config = json.loads(config_file.read_text())
    config["scheme"]["kind"] = "toy_masked_packed_shamir"
    config_file.write_text(json.dumps(config))
    shutil.copy(here / "toy_unmask_s.py", root / "benchmark/layers/unmask_s.py")
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    for metric in manifest["per_layer"]:
        if metric["name"] == TOY_SHARED_METRIC:
            metric["workloads"].append(name)
    manifest["per_layer"].append({
        "name": "unmask.s", "unit": "s", "better": "lower", "source": "program_span",
        "layer": "recipient unmask", "moves": "round_s", "workloads": [name],
    })
    (root / "BENCHMARK.json").write_text(json.dumps(manifest, indent=1))
    return name


@contextlib.contextmanager
def rounds_importable_from(root):
    """Inside, ``benchmark.rounds.<name>`` is also looked for under ``root``:
    in a checkout a round dropped into ``benchmark/rounds/`` is importable as
    it lies; in a temporary copy the package is this repo's, so the copy's
    directory joins its path. What was imported from there goes again."""
    import benchmark.rounds as package

    directory = str(pathlib.Path(root) / "benchmark/rounds")
    package.__path__.append(directory)
    try:
        yield
    finally:
        package.__path__.remove(directory)
        for name, module in list(sys.modules.items()):
            if (getattr(module, "__file__", None) or "").startswith(directory):
                del sys.modules[name]
                delattr(package, name.rpartition(".")[2])
