"""The manifest against the benchmark's contract, and every file a cell needs
found by the names the manifest gives. What one configuration and one cell
are held to is in ``cell_checks.py``, as functions of the tree and the name:
here they run on this repo's manifest, one case each, and in
``test_benchmark_rounds.py`` on a tree with a new kind of round dropped in."""

import json
import pathlib
import re

import pytest

import cell_checks
from benchmark import harness
from cell_checks import NAME, one_line

REPO = pathlib.Path(__file__).resolve().parents[2]
MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
PATH = re.compile(r"[A-Za-z0-9_.\-/]{1,200}")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
CELLS = MANIFEST["workloads"]


def test_manifest_has_exactly_the_contract_keys():
    assert set(MANIFEST) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer",
    }
    assert len((REPO / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_command_and_paths():
    command, paths = MANIFEST["command"], MANIFEST["paths"]
    assert 1 <= len(command) <= 32 and all(one_line(w) for w in command)
    assert 1 <= len(paths) <= 16
    for path in paths:
        assert PATH.fullmatch(path) and not path.startswith("/") and ".." not in path
        assert (REPO / path).is_dir()
    for word in command:
        assert not word.startswith("/") and ".." not in word
        if (REPO / word).exists():
            assert any(word.startswith(p + "/") for p in paths), word


def test_run_seconds_fits_a_full_check_with_24_cells():
    seconds = MANIFEST["run_seconds"]
    assert isinstance(seconds, int) and 10 <= seconds <= 51
    runs = 2 + 14 * 24
    assert runs * (seconds + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    end_to_end = metric in MANIFEST["end_to_end"]
    allowed = {"name", "unit", "better", "source", "workloads"}
    allowed |= {"bound"} if end_to_end else {"layer", "moves"}
    assert set(metric) <= allowed and allowed - {"workloads"} <= set(metric)
    assert NAME.fullmatch(metric["name"]) and UNIT.fullmatch(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    if end_to_end:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert metric["source"] in SOURCES
        assert one_line(metric["layer"])
        assert metric["moves"] in {m["name"] for m in MANIFEST["end_to_end"]}
        if metric["name"].endswith("_roofline"):
            assert metric["unit"] == "%"
    for cell in metric.get("workloads", ()):
        assert cell in {w["name"] for w in CELLS}


def test_metric_names_are_unique_and_setup_s_is_there():
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    setup = [m for m in MANIFEST["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and setup[0]["bound"] <= 0.1 and setup[0]["unit"] == "s"
    assert 1 <= len(MANIFEST["end_to_end"]) <= 16 and 1 <= len(MANIFEST["per_layer"]) <= 128


@pytest.mark.parametrize("config", [c["name"] for c in MANIFEST["configs"]])
def test_config_entry_and_file(config):
    cell_checks.check_config(REPO, config)


@pytest.mark.parametrize("scheme,privacy,reconstruction", [
    ({"kind": "packed_shamir", "secret_count": 5, "privacy_threshold": 2, "share_count": 8}, 2, 7),
    ({"kind": "basic_shamir", "privacy_threshold": 3, "share_count": 8}, 3, 4),
    ({"kind": "additive", "share_count": 3}, 2, 3),
])
def test_stated_thresholds_follow_the_scheme_block(scheme, privacy, reconstruction):
    assert cell_checks.stated_thresholds(scheme) == (privacy, reconstruction)


@pytest.mark.parametrize("stated", ["privacy_threshold", "reconstruction_threshold"])
def test_a_configuration_that_states_another_threshold_than_its_scheme_is_refused(tmp_path, stated):
    import bench_tree

    root = bench_tree.copy_benchmark(tmp_path / "copy")
    name = bench_tree.add_cell(root, *bench_tree.TINY_CELLS[0])
    cell_checks.check_config(root, f"{name}-config")
    file = root / "benchmark/configs" / f"{name}-config.json"
    config = json.loads(file.read_text())
    config["guarantees"][stated] += 1
    file.write_text(json.dumps(config))
    with pytest.raises(AssertionError, match="scheme"):
        cell_checks.check_config(root, f"{name}-config")


def test_config_names_and_files_are_unique():
    configs = MANIFEST["configs"]
    assert 1 <= len(configs) <= 24
    assert len({c["name"] for c in configs}) == len(configs)
    assert len({c["file"] for c in configs}) == len(configs)


@pytest.mark.parametrize("cell", [w["name"] for w in CELLS])
def test_cell_entry_and_its_files_are_found_by_name(cell):
    cell_checks.check_cell(REPO, cell)


def test_cells_are_unique_and_few_take_four_chips():
    assert 2 <= len(CELLS) <= 24
    assert len({w["name"] for w in CELLS}) == len(CELLS)
    assert len({(w["config"], w["traffic"]) for w in CELLS}) == len(CELLS)
    four = sum(w["chips"] == 4 for w in CELLS)
    assert four <= max(1, len(CELLS) // 4)


def test_layer_files_agree_with_the_manifest():
    """Unit, layer and the metric moved are the manifest's. Which cells report
    a metric is said once, in the manifest (``workloads``): a layer file has
    no list to keep equal, so a cell that shares a metric's code adds its name
    there and edits no file."""
    layers = harness.load_layers(REPO)
    listed = {m["name"]: m for m in MANIFEST["per_layer"]}
    assert set(layers) == set(listed)
    for name, module in layers.items():
        entry = listed[name]
        assert (module.unit, module.layer, module.moves) == (
            entry["unit"], entry["layer"], entry["moves"]
        ), name
        assert not hasattr(module, "cells"), name
        assert isinstance(module.reads_spans, tuple), name


def test_the_spans_every_round_opens_are_those_the_cell_wide_metrics_read():
    """``benchmark.rounds`` says which spans every round opens; they are what
    the metrics with no list of cells read, the harness's own span aside."""
    from benchmark import rounds

    layers = harness.load_layers(REPO)
    read = {
        span
        for metric in MANIFEST["per_layer"] if "workloads" not in metric
        for span in layers[metric["name"]].reads_spans
    }
    assert read - {harness.ROUND_SPAN} == set(rounds.CELL_WIDE_SPANS)


def test_files_under_paths_are_named_from_a_names_characters():
    for path in MANIFEST["paths"]:
        for file in (REPO / path).rglob("*"):
            relative = file.relative_to(REPO).as_posix()
            if "__pycache__" in relative or "/out/" in relative:
                continue
            assert PATH.fullmatch(relative), relative


def test_peaks_table_names_its_source_and_refuses_an_unknown_kind():
    peaks = harness.load_peaks(REPO, "TPU v5 lite")
    assert peaks["hbm_bytes_per_s"] == 819e9 and peaks["int8_ops_per_s"] == 393e12
    table = json.loads((REPO / "benchmark/peaks.json").read_text())
    assert "Google Cloud" in table["source"]
    with pytest.raises(harness.HarnessError):
        harness.load_peaks(REPO, "TPU v9 imaginary")


def test_models_count_what_a_chunk_step_needs():
    import types

    from benchmark import models

    peaks = harness.load_peaks(REPO, "TPU v5 lite")
    plan = types.SimpleNamespace(
        modulus=(1 << 30) + 7, input_size=5, rand_size=2, share_count=8, n_batches=10_000
    )
    assert models.limb_count(plan.modulus) == 5 and models.limb_count((1 << 60) + 1) == 9
    chunk_bytes = 2000 * 50_000 * 4
    assert models.chunk_step_bytes(chunk_bytes, 100) == chunk_bytes + 200
    ops = models.chunk_step_int8_ops(True, 2000, plan)
    assert ops == 2 * 2000 * 10_000 * 7 * 8 * 25
    assert models.chunk_step_int8_ops(False, 2000, plan) == 0
    seconds, binds = models.least_seconds(chunk_bytes, ops, peaks)
    assert binds == "hbm" and seconds == pytest.approx(chunk_bytes / 819e9)
    assert models.least_seconds(1, ops, peaks) == (pytest.approx(ops / 393e12), "int8")
