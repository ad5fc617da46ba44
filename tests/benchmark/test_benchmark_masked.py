"""The masked round (``benchmark/rounds/masked_fold.py``) at a tiny size on
the CPU, through the harness as the chip runs it: the cell's comparisons as
on the chip, a round that skips the mask and a wrong fold caught, and what the
rounds kernel has to move at the least (``benchmark/models_chacha.py``), and
the round's four layer metrics, which wait in ``masked_layers/`` for the
``benchmark`` PR that may edit the one test that lists which metrics a trace
without the program's names leaves silent (``test_benchmark_trace_reduce.py``;
PERF.md section 7). The manifest's parametrised checks and the compile
rehearsal hold ``c5-masked`` itself, by its name."""

import json
import pathlib
import shutil
import time
import types

import pytest

import bench_tree
import cell_checks
from benchmark import harness, models_chacha

REPO = bench_tree.REPO
CELL, CONFIG, TRAFFIC = "c5-masked", "c5-w61-d100k-chacha", "masked-wide"
TINY = "tiny-c5-masked"
DIM, ROWS, CHUNK = 62, 24, 6
#: the comparisons of the line, the harness's four and the round's three
COMPARED = [
    "warmup_mismatched", "rounds_mismatched", "rounds_repeated", "compiles_in_window",
    "unmasked_reveals", "slack_exhausted_rows", "mask_parts_mismatched",
]


def add_tiny_masked(root, name=TINY, **traffic_changes):
    """A tiny twin of ``c5-masked`` as new files; every metric that lists the
    cell lists the twin too."""
    bench_tree.add_cell(
        root, name, CONFIG, TRAFFIC, DIM, ROWS, 1, CHUNK, None,
        **{"recipient_chunk": CHUNK, **traffic_changes},
    )
    config_file = root / "benchmark/configs" / f"{name}-config.json"
    config = json.loads(config_file.read_text())
    config["masking"]["dimension"] = DIM
    config_file.write_text(json.dumps(config))
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    for metric in manifest["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return name


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = bench_tree.copy_benchmark(tmp_path_factory.mktemp("masked") / "copy")
    add_tiny_masked(root)
    return root


@pytest.fixture(autouse=True)
def device_combine(monkeypatch):
    """At this size the recipient would sum the masks on the host; the cell
    is about the device fold."""
    from sda_tpu.crypto.masking import ChaChaMasker

    monkeypatch.setattr(ChaChaMasker, "DEVICE_COMBINE_THRESHOLD", 1)


def run(root, workload, trace=False, seconds=0.3, seed=5):
    import jax

    return harness.run_cell(
        root, workload, seed, seconds, trace, jax.devices("cpu"),
        time.perf_counter(), out_dir=root / "out", log=lambda message: None,
    )


def test_masked_rounds_agree_exactly_and_compare_what_the_chip_compares(tree):
    line = run(tree, TINY)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    assert list(line["compared"]) == COMPARED
    assert all(c == {"value": 0, "limit": 0} for c in line["compared"].values())
    assert set(line["metrics"]) == {"round_s", "setup_s"}
    record = json.loads((tree / "out" / f"rounds-{TINY}-seed5-trace0.json").read_text())
    assert list(record["spans"]) == ["dispatch", "fold", "fetch", "epilogue", "unmask", "check"]
    assert len(record["spans"]["unmask"]) == line["attempted"]


def test_the_tiny_twin_passes_the_checks_every_cell_passes(tree):
    cell_checks.check_config(tree, f"{TINY}-config")
    cell_checks.check_cell(tree, TINY)


def test_masked_round_runs_two_programs_the_masked_step_first(tree):
    import jax

    cell = harness.load_cell(tree, TINY)
    programs = harness.round_of(cell).steps(cell, jax.devices("cpu"))
    assert [jitted.__name__ for jitted, _args in programs] == ["masked_step", "_fold_chunk"]
    (_acc, chunk, _key, _index), fold_args = programs[0][1], programs[1][1]
    assert chunk.shape == (CHUNK, DIM)
    assert fold_args[0].shape == (CHUNK, 4) and fold_args[1:3] == (DIM, cell_modulus(cell))
    for jitted, args in programs:
        jitted.lower(*args)


def cell_modulus(cell):
    from benchmark.rounds import packed_fold

    return packed_fold.build_program(cell, None).modulus


def test_a_traced_masked_run_reports_the_span_metrics_and_invents_no_device_number(tree):
    """The metrics every cell reports read the three spans every round opens;
    the five whose lists the cell joined read the program's names, which a
    trace with no device plane does not hold."""
    line = run(tree, TINY, trace=True)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"engine.fold_s", "epilogue.s", "elems_per_s"}
    listed = {m["name"] for m in harness.load_cell(tree, TINY).per_layer}
    assert {"engine.input_s", "engine.rand_s", "epilogue.recombine_s",
            "epilogue.share_matmul_s", "epilogue.reconstruct_s"} <= listed


def test_a_round_that_skips_the_mask_is_caught_by_unmasked_reveals(tree):
    name = add_tiny_masked(
        tree, "tiny-unmasked", masked_engine_call="faulty_masked.unmasking_chunk_engine"
    )
    line = run(tree, name)
    assert line["correct"] is False
    reveals = line["compared"]["unmasked_reveals"]
    assert reveals["limit"] == 0 and reveals["value"] == line["attempted"] + 1


def test_a_wrong_fold_is_caught_by_the_mask_parts(tree):
    name = add_tiny_masked(tree, "tiny-wrong-fold", recipient_fold="faulty_masked.off_by_one_fold")
    line = run(tree, name)
    assert line["compared"]["mask_parts_mismatched"] == {"value": 2, "limit": 0}
    assert line["failed"] == 0, "the timed combine runs the program's own fold"
    assert line["correct"] is False


def test_a_short_window_is_counted_and_fails_the_run(tree, monkeypatch):
    from sda_tpu.ops import chacha_pallas

    monkeypatch.setattr(chacha_pallas, "_window_pairs", lambda dim, modulus: dim + 1)
    chacha_pallas._FOLD_CHUNK_JIT = None  # a fold traced with the real window is not this one
    try:
        line = run(tree, TINY)
    finally:
        chacha_pallas._FOLD_CHUNK_JIT = None
    assert line["compared"]["slack_exhausted_rows"]["value"] > 0
    assert line["correct"] is False


def test_the_masked_round_refuses_a_configuration_that_masks_nothing(tmp_path):
    root = bench_tree.copy_benchmark(tmp_path / "copy")
    name = bench_tree.add_cell(
        root, "no-masking", "c5-w61-d100k", TRAFFIC, DIM, ROWS, 1, CHUNK, None
    )
    with pytest.raises(harness.HarnessError, match="masking"):
        run(root, name)


def test_the_references_import_nothing_of_the_program():
    for file in ("reference.py", "reference_chacha.py", "models_chacha.py"):
        assert "sda_tpu" not in (REPO / "benchmark" / file).read_text().replace(
            "from ``sda_tpu``", ""
        ), file


def test_models_chacha_counts_what_the_expansion_needs():
    p61 = (1 << 60) + 225
    assert models_chacha.rejected_share(p61) == pytest.approx(1 / 16, rel=1e-3)
    assert models_chacha.rejected_share(1 << 63) == 0.5
    # 100 000 accepted of 106 667 draws, eight draws a block
    assert models_chacha.blocks_per_seed(100_000, p61) == 13_334
    assert models_chacha.rounds_kernel_bytes(500, 100_000, p61) == 500 * 13_334 * 128
    assert models_chacha.seeds_expanded_per_round(10_000) == 20_000


# ---------------------------------------------------------------------------
# The round's own layer metrics, dropped into a copy as the files a
# ``benchmark`` PR would add under ``benchmark/layers/``
# ---------------------------------------------------------------------------

LAYER_FILES = pathlib.Path(__file__).resolve().parent / "masked_layers"
MASKED_METRICS = ["mask.step_s", "unmask.stage_s", "unmask.expand_s", "chacha_rounds_roofline"]


@pytest.fixture(scope="module")
def tree_with_layers(tmp_path_factory):
    root = bench_tree.copy_benchmark(tmp_path_factory.mktemp("masked_layers") / "copy")
    for file in sorted(LAYER_FILES.glob("*.py")):
        shutil.copy(file, root / "benchmark/layers" / file.name)
    add_tiny_masked(root)
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    layers = harness.load_layers(root)
    for name in MASKED_METRICS:
        module = layers[name]
        source = "program_span" if module.reads_spans else "device_trace"
        manifest["per_layer"].append({
            "name": name, "unit": module.unit,
            "better": "higher" if module.unit == "%" else "lower", "source": source,
            "layer": module.layer, "moves": module.moves, "workloads": [TINY],
        })
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root


def test_the_masked_layer_files_are_layer_files_and_pass_the_cell_checks(tree_with_layers):
    layers = harness.load_layers(tree_with_layers)
    assert set(MASKED_METRICS) <= set(layers)
    assert [layers[n].layer for n in MASKED_METRICS] == [
        "mask stage", "recipient unmask", "recipient unmask", "kernels"
    ]
    cell_checks.check_cell(tree_with_layers, TINY)


def test_a_traced_cpu_run_reports_the_unmask_stage_and_no_device_number(tree_with_layers):
    line = run(tree_with_layers, TINY, trace=True)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"engine.fold_s", "epilogue.s", "elems_per_s", "unmask.stage_s"}
    record = json.loads((tree_with_layers / "out" / f"rounds-{TINY}-seed5-trace1.json").read_text())
    stage = line["metrics"]["unmask.stage_s"]
    assert stage["unit"] == "s" and min(record["spans"]["unmask"]) <= stage["value"] <= max(
        record["spans"]["unmask"]
    )


def test_the_device_metrics_of_the_masked_round_read_scopes_and_the_kernels_operations(
    tree_with_layers,
):
    """Their arithmetic on a made-up trace and scope report, which a CPU run
    never reaches: two chips, the busier one decides."""
    layers = harness.load_layers(tree_with_layers)
    ms = 1_000_000  # ns
    trace = types.SimpleNamespace(
        chips=[0, 1],
        ops={
            0: [("jit_masked_step/chacha_rounds.1", 0, 4 * ms),
                ("jit_masked_step/fusion.2", 4 * ms, 9 * ms),
                ("jit__fold_chunk/chacha_rounds.3", 9 * ms, 15 * ms)],
            1: [("jit_masked_step/chacha_rounds.1", 0, 2 * ms)],
        },
    )
    report = {
        "busiest_chip": "0",
        "chips": {"0": {
            "by_scope": {"fabric.mask": 0.25, "fabric.unmask": 0.5},
            "by_path": {"fabric.mask/expand": 0.2, "fabric.unmask/expand": 0.375,
                        "fabric.unmask/sum": 0.125},
        }},
    }
    p61, lines = (1 << 60) + 225, []
    context = types.SimpleNamespace(
        rounds=2, scopes=report, config={"dim": 100_000}, plan=types.SimpleNamespace(modulus=p61),
        traffic=types.SimpleNamespace(rows=10, passes=1), peaks={"hbm_bytes_per_s": 819e9},
        log=lines.append,
    )
    assert layers["mask.step_s"].reduce([], trace, context) == 0.25
    assert layers["unmask.expand_s"].reduce([], trace, context) == 0.375
    share = layers["chacha_rounds_roofline"].reduce([], trace, context)
    least = 2 * models_chacha.rounds_kernel_bytes(20, 100_000, p61) / 819e9
    assert share == pytest.approx(100 * least / 0.010) and 0 < share < 100
    assert "13334 blocks" in lines[0]
    # nothing to read: no trace, no report, no kernel in the trace
    context.scopes = None
    empty = types.SimpleNamespace(chips=[0], ops={0: [("jit_step/fusion.1", 0, ms)]})
    for name in ("mask.step_s", "unmask.expand_s", "chacha_rounds_roofline"):
        assert layers[name].reduce([], None, context) is None
        assert layers[name].reduce([], empty, context) is None
    assert layers["unmask.stage_s"].reduce([], None, context) is None
