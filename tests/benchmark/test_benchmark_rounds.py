"""The harness end to end at a tiny size on the CPU: the four cells' rounds
agree exactly with the plain reference, faults are caught, and new cells,
configurations, traffic mixes and layer metrics are new files. The CPU
devices are handed in here; the benchmark has no option for them."""

import json
import os
import pathlib
import subprocess
import sys
import time

import numpy as np
import pytest

import bench_tree
import cell_checks
from benchmark import harness, reference
from benchmark.rounds import packed_fold

REPO = bench_tree.REPO
TINY = [cell[0] for cell in bench_tree.TINY_CELLS]
#: the four cells' spans: the harness's and those of their round
SPANS = ("round", *packed_fold.span_names)
#: the metrics that read the program's own names (PR 27)
NEW_LAYERS = (
    "engine.input_s", "engine.rand_s", "engine.layout_s", "engine.share_matmul_s",
    "engine.unscoped_s", "epilogue.recombine_s", "epilogue.share_matmul_s",
    "epilogue.reconstruct_s",
)


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return bench_tree.tiny_tree(tmp_path_factory.mktemp("benchmark_copy"))


def run(tree, workload, trace=False, seconds=0.2, seed=5):
    import jax

    return harness.run_cell(
        tree, workload, seed, seconds, trace, jax.devices("cpu"),
        time.perf_counter(), out_dir=tree / "out", log=lambda message: None,
    )


@pytest.mark.parametrize("workload", TINY)
def test_rounds_agree_exactly_with_the_reference(tree, workload):
    line = run(tree, workload)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device", "compared"]
    assert all(c == {"value": 0, "limit": 0} for c in line["compared"].values())
    assert len(line["compared"]) == 4
    assert set(line["metrics"]) == {"round_s", "setup_s"}
    for name, metric in line["metrics"].items():
        assert metric["value"] > 0 and metric["unit"]
    assert line["device"]["platform"] == "cpu"
    record = json.loads((tree / "out" / f"rounds-{workload}-seed5-trace0.json").read_text())
    assert len(record["round_s_each"]) == line["attempted"]
    assert record["round_s"] == pytest.approx(np.median(record["round_s_each"]))


@pytest.mark.parametrize("workload", TINY)
def test_traced_run_reports_layer_metrics_and_invents_no_device_number(tree, workload):
    line = run(tree, workload, trace=True)
    assert line["correct"] is True
    # spans exist on the CPU; a trace without a device plane gives nothing
    assert set(line["metrics"]) == {"engine.fold_s", "epilogue.s", "elems_per_s"}
    record = json.loads((tree / "out" / f"rounds-{workload}-seed5-trace1.json").read_text())
    # the same arithmetic as the record's, which the spread study reads
    assert line["metrics"]["elems_per_s"]["value"] == pytest.approx(record["elems_per_s"], rel=1e-3)
    assert "busy_s" not in line["device"] and "breakdown" not in line
    assert not list((tree / "out").glob("trace-*")), "the trace is not kept"


@pytest.mark.parametrize("workload", TINY)
def test_a_flipped_aggregate_element_fails_the_run(tree, workload):
    cell = next(c for c in bench_tree.TINY_CELLS if c[0] == workload)
    name = bench_tree.add_cell(
        tree, f"flip-{workload}", *cell[1:], reconstruct="faulty.flipped_reconstruct"
    )
    line = run(tree, name)
    assert line["correct"] is False
    assert line["failed"] == line["attempted"] == harness.MAX_CONSECUTIVE_FAILURES


def test_equal_clerk_sums_in_two_rounds_fail_the_run(tree):
    cell = bench_tree.TINY_CELLS[0]
    name = bench_tree.add_cell(
        tree, "keyless", *cell[1:], engine_call="faulty.keyless_chunk_engine"
    )
    line = run(tree, name)
    assert line["failed"] == 0, "the aggregate does not depend on the share keys"
    assert line["correct"] is False


def test_a_round_that_raises_is_a_failed_round(tree):
    cell = bench_tree.TINY_CELLS[0]
    name = bench_tree.add_cell(
        tree, "raising", *cell[1:], epilogue_call="faulty.late_raising_epilogue"
    )
    line = run(tree, name, seconds=5.0)
    assert line["correct"] is False
    assert line["attempted"] == 1 + harness.MAX_CONSECUTIVE_FAILURES
    assert line["failed"] == harness.MAX_CONSECUTIVE_FAILURES


def test_new_cells_are_new_files_and_edit_none_that_was_there(tree):
    originals, copies = bench_tree.digests(REPO, ["benchmark"]), bench_tree.digests(tree, ["benchmark"])
    assert originals
    assert {path: copies[path] for path in originals} == originals
    kept = json.loads((REPO / "BENCHMARK.json").read_text())
    grown = json.loads((tree / "BENCHMARK.json").read_text())
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        assert grown[key][: len(kept[key])] == kept[key], "entries are added, none changed"
    assert len(grown["workloads"]) >= len(kept["workloads"]) + len(TINY)


def test_a_new_layer_metric_is_a_new_file(tmp_path):
    root = bench_tree.copy_benchmark(tmp_path / "copy")
    name = bench_tree.add_cell(root, *bench_tree.TINY_CELLS[3])
    (root / "benchmark/layers/check_s.py").write_text(
        "import statistics\n"
        "name, unit, layer, moves = 'check.s', 's', 'reference check', 'round_s'\n"
        "reads_spans = ('check',)\n"
        "def reduce(spans, trace, cell):\n"
        "    return statistics.median(s.seconds for s in spans if s.name == 'check')\n"
    )
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["per_layer"].append({
        "name": "check.s", "unit": "s", "better": "lower", "source": "program_span",
        "layer": "reference check", "moves": "round_s", "workloads": [name],
    })
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    line = run(root, name, trace=True)
    assert line["metrics"]["check.s"]["value"] > 0
    other = bench_tree.add_cell(root, *bench_tree.TINY_CELLS[0])
    assert "check.s" not in run(root, other, trace=True)["metrics"]


# ---------------------------------------------------------------------------
# A new kind of round is new files, in everything under the benchmark's paths
# ---------------------------------------------------------------------------

TOY = "toy-masked"


@pytest.fixture(scope="module")
def toy_tree(tmp_path_factory):
    """What the next configuration's PR does, in a copy of everything under
    the benchmark's ``paths``: ``bench_tree.add_toy_cell`` drops the toy
    round in as ``benchmark/rounds/toy_masked.py`` with its files. Yields
    ``(root, digests before)``."""
    root = bench_tree.copy_benchmark(tmp_path_factory.mktemp("toy") / "copy", bench_tree.PATHS)
    before = bench_tree.digests(root)
    bench_tree.add_toy_cell(root, TOY, "toy_masked")
    with bench_tree.rounds_importable_from(root):
        yield root, before


def test_a_new_kind_of_round_is_a_new_file(toy_tree):
    """A round with a stage after reconstruct that needs state the step handed
    on, under a span of its own, with a second device program in the window,
    comes as one module under ``benchmark/rounds/``, a configuration, a
    traffic file that names the module, one ``workloads`` entry, a layer file
    and its name in one shared metric's list. No file that was there, under
    either of the benchmark's paths, is edited."""
    root, before = toy_tree
    line = run(root, TOY)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    record = json.loads((root / "out" / f"rounds-{TOY}-seed5-trace0.json").read_text())
    assert list(record["spans"]) == ["dispatch", "fold", "fetch", "epilogue", "unmask", "check"]
    assert len(record["spans"]["unmask"]) == line["attempted"]
    assert all(seconds > 0 for seconds in record["spans"]["unmask"])
    cell = harness.load_cell(root, TOY)
    module = harness.round_of(cell)
    assert module.__name__ == cell.traffic.round == "benchmark.rounds.toy_masked"
    assert pathlib.Path(module.__file__).parent == root / "benchmark/rounds"
    assert harness.span_names(cell)[0] == "round" and "unmask" in harness.span_names(cell)
    import jax

    programs = module.steps(cell, jax.devices("cpu"))
    assert [jitted.__name__ for jitted, _args in programs] == ["masked_step", "unmask_fold"]
    after = bench_tree.digests(root)
    assert {path: after[path] for path in before} == before, "a file that was there was edited"
    assert before == bench_tree.digests(REPO)
    assert any(path.startswith("tests/benchmark/") for path in before)
    assert set(after) - set(before) == {
        "benchmark/rounds/toy_masked.py", "benchmark/layers/unmask_s.py",
        f"benchmark/configs/{TOY}-config.json", f"benchmark/traffic/{TOY}-traffic.json",
    }
    kept = json.loads((REPO / "BENCHMARK.json").read_text())
    grown = json.loads((root / "BENCHMARK.json").read_text())
    assert [m["name"] for m in grown["per_layer"]] == [m["name"] for m in kept["per_layer"]] + ["unmask.s"]
    for was, now in zip(kept["per_layer"], grown["per_layer"]):
        shared = was["name"] == bench_tree.TOY_SHARED_METRIC
        assert now == (dict(was, workloads=[*was["workloads"], TOY]) if shared else was)
    for key in ("configs", "workloads", "end_to_end"):
        assert grown[key][: len(kept[key])] == kept[key], "entries are added, none changed"


def test_the_new_rounds_cell_passes_the_checks_the_four_cells_pass(toy_tree):
    root, _before = toy_tree
    cell_checks.check_config(root, f"{TOY}-config")
    cell_checks.check_cell(root, TOY)
    for cell in json.loads((REPO / "BENCHMARK.json").read_text())["workloads"]:
        cell_checks.check_cell(root, cell["name"])


def test_a_traced_run_of_the_new_round_reports_its_own_and_the_shared_metrics(toy_tree):
    """Idle gaps are named and host events kept by the round's own spans; its
    own layer file reads its ``unmask`` span, and the cell-wide metrics read
    the three spans every round opens."""
    root, _before = toy_tree
    traced = run(root, TOY, trace=True)
    assert traced["correct"] is True
    assert set(traced["metrics"]) == {"engine.fold_s", "epilogue.s", "elems_per_s", "unmask.s"}
    assert traced["metrics"]["unmask.s"]["value"] > 0


def test_a_rounds_own_comparisons_reach_the_line(toy_tree, monkeypatch):
    """The round compares what only it knows (the reveal before unmasking
    must not equal the plain aggregate); the harness writes that into the
    line after its own four and counts it into ``correct``."""
    root, _before = toy_tree
    line = run(root, TOY)
    assert list(line["compared"]) == [
        "warmup_mismatched", "rounds_mismatched", "rounds_repeated", "compiles_in_window",
        "unmasked_reveals",
    ]
    assert line["compared"]["unmasked_reveals"] == {"value": 0, "limit": 0}
    assert line["correct"] is True
    # the stage did work: with masks that are all nought the clerks reveal
    # the plain aggregate itself, every round still matches, and only the
    # round's own comparison says that nothing was masked
    module = harness.round_of(harness.load_cell(root, TOY))
    monkeypatch.setattr(module, "MASK_BELOW", 1)
    bare = run(root, TOY)
    assert bare["failed"] == 0 and bare["compared"]["rounds_mismatched"]["value"] == 0
    assert bare["compared"]["unmasked_reveals"]["value"] == bare["attempted"] + 1
    assert bare["correct"] is False


def test_a_round_may_not_take_the_name_of_a_comparison_of_the_harness(toy_tree, monkeypatch):
    root, _before = toy_tree
    module = harness.round_of(harness.load_cell(root, TOY))
    monkeypatch.setattr(
        module.Session, "compared", lambda self: {"rounds_mismatched": {"value": 0, "limit": 9}}
    )
    with pytest.raises(harness.HarnessError, match="rounds_mismatched"):
        run(root, TOY)


def test_a_round_that_lacks_a_span_a_listed_metric_reads_fails_the_cell_check(tmp_path):
    """A cell whose round does not open what a metric it reports reads is
    found here, on the CPU, by the metric's and the span's names: on the chip
    the traced line would lack the metric and the run be refused."""
    source = (pathlib.Path(__file__).parent / "toy_round.py").read_text()
    root = bench_tree.copy_benchmark(tmp_path / "copy")
    name = bench_tree.add_toy_cell(
        root, "toy-renamed", "toy_renamed", source=source.replace('"epilogue"', '"reveal"')
    )
    with bench_tree.rounds_importable_from(root):
        assert "reveal" in harness.span_names(harness.load_cell(root, name))
        with pytest.raises(AssertionError, match="'epilogue.s', which reads the span 'epilogue'"):
            cell_checks.check_cell(root, name)
        # the same through a metric with a list of cells, by the metric's name:
        # a cell of the plain round listed under the toy's own metric
        other = bench_tree.add_cell(root, *bench_tree.TINY_CELLS[0])
        cell_checks.check_cell(root, other)
        manifest = json.loads((root / "BENCHMARK.json").read_text())
        for metric in manifest["per_layer"]:
            if metric["name"] == "unmask.s":
                metric["workloads"].append(other)
        (root / "BENCHMARK.json").write_text(json.dumps(manifest))
        with pytest.raises(AssertionError, match="'unmask.s', which reads the span 'unmask'"):
            cell_checks.check_cell(root, other)


def add_masked_cell(root, name):
    """One tiny cell whose configuration states the toy round's scheme kind
    under a traffic file that names no round at all."""
    bench_tree.add_cell(root, name, *bench_tree.TINY_CELLS[0][1:])
    config_file = root / "benchmark/configs" / f"{name}-config.json"
    config = json.loads(config_file.read_text())
    config["scheme"]["kind"] = "toy_masked_packed_shamir"
    config_file.write_text(json.dumps(config))
    return name


def test_a_scheme_kind_the_round_does_not_know_is_refused_by_the_round(tmp_path):
    """The same configuration under a traffic file that names no round, so
    the default: ``packed_fold`` refuses the kind. The harness has no opinion."""
    root = bench_tree.copy_benchmark(tmp_path / "copy")
    name = add_masked_cell(root, "masked-under-the-plain-round")
    assert harness.load_cell(root, name).traffic.round == "benchmark.rounds.packed_fold"
    with pytest.raises(harness.HarnessError, match="unknown scheme kind") as refused:
        run(root, name)
    assert pathlib.Path(refused.traceback[-1].path).name == "packed_fold.py"
    assert "sda_tpu" not in (REPO / "benchmark/harness.py").read_text()
    assert not any(hasattr(harness, gone) for gone in ("build_program", "Program", "Session"))


def test_a_traffic_file_that_names_no_such_round_is_refused(tmp_path):
    root = bench_tree.copy_benchmark(tmp_path / "copy")
    name = bench_tree.add_cell(
        root, "no-round", *bench_tree.TINY_CELLS[0][1:], round="benchmark.rounds.not_there"
    )
    with pytest.raises(harness.HarnessError, match="no round"):
        run(root, name)


def test_a_cell_whose_metric_has_no_layer_file_is_refused(tmp_path):
    root = bench_tree.copy_benchmark(tmp_path / "copy")
    name = bench_tree.add_cell(root, *bench_tree.TINY_CELLS[3])
    (root / "benchmark/layers/epilogue_s.py").unlink()
    with pytest.raises(harness.HarnessError, match="epilogue.s"):
        run(root, name)


def test_a_cell_with_fewer_devices_than_chips_is_refused(tree):
    import jax

    with pytest.raises(harness.HarnessError, match="needs 4 devices"):
        harness.run_cell(
            tree, "tiny-c5-sumfirst-x4", 0, 0.1, False, jax.devices("cpu")[:1],
            time.perf_counter(), out_dir=tree / "out",
        )


def run_command(cwd, workload="c4-sumfirst"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", workload, "--seed", "0",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_with_no_tpu_the_command_exits_nonzero_and_prints_no_result():
    done = run_command(REPO)
    assert done.returncode == 2, done.stderr[-2000:]
    assert done.stdout == ""
    assert "No result" in done.stderr


def test_without_the_program_the_command_exits_nonzero_and_prints_no_result(tmp_path):
    root = bench_tree.copy_benchmark(tmp_path / "bare")
    done = run_command(root)
    assert done.returncode != 0 and done.stdout == ""


@pytest.mark.parametrize("bits,dtype", [(30, "int32"), (60, "int64")])
def test_reference_sums_are_exact(bits, dtype):
    import jax

    jax.config.update("jax_enable_x64", True)
    rng = np.random.default_rng(bits)
    modulus = (1 << bits) + 1093
    block = rng.integers(0, 1 << bits, size=(40, 70), dtype=np.int64)
    want = np.array([sum(int(v) for v in col) * 3 % modulus for col in block.T])
    halves = np.array(reference.half_sums(jax.numpy.asarray(block.astype(dtype))))
    columns = np.asarray(reference.strided_columns(block))
    got = reference.aggregate(halves, columns, modulus, passes=3, rows=40)
    assert got.dtype == np.int64 and np.array_equal(got, want)
    halves[0, 0] += 1
    with pytest.raises(AssertionError, match="disagrees with itself"):
        reference.aggregate(halves, columns, modulus, passes=3, rows=40)


def test_spread_is_quartile_distance_over_median():
    # as the driver takes them: statistics.quantiles(values, n=4), 1.5 and 4.5 here
    assert harness.spread([1.0, 2.0, 3.0, 4.0, 5.0]) == pytest.approx(3.0 / 3.0)
    assert harness.spread([2.0]) == 0.0


def test_a_traced_run_with_a_device_plane_reports_all_layers_and_the_breakdown(tmp_path, monkeypatch):
    """The traced branch past the trace's reduction, which a CPU trace never
    reaches: the chip-recorded trace stands in for this run's."""
    from benchmark import trace_reduce

    raw = json.loads((pathlib.Path(__file__).parent / "recorded-trace-x4.json").read_text())
    reduced = trace_reduce.reduce(raw, SPANS)
    peaks = harness.load_peaks(REPO, "TPU v5 lite")
    monkeypatch.setattr(harness, "_read_trace", lambda trace_dir, names, log: (raw, reduced))
    monkeypatch.setattr(harness, "load_peaks", lambda root, kind: peaks)
    tree = bench_tree.copy_benchmark(tmp_path / "copy")  # its manifest is edited below
    name = bench_tree.add_cell(tree, "x4-layers", *bench_tree.TINY_CELLS[1][1:])
    manifest = json.loads((tree / "BENCHMARK.json").read_text())
    for metric in manifest["per_layer"]:
        if "workloads" in metric:
            metric["workloads"].append(name)
    (tree / "BENCHMARK.json").write_text(json.dumps(manifest))
    line = run(tree, name, trace=True)
    listed = {m["name"] for m in manifest["per_layer"]}
    # the CPU reports no peak; the recorded trace holds none of the program's
    # names, and its operations are absent from the text compiled here
    assert set(NEW_LAYERS) <= listed
    assert set(line["metrics"]) == listed - {"device.peak_gib"} - set(NEW_LAYERS)
    assert line["device"]["busy_s"] == pytest.approx(reduced.mean_busy_seconds())
    assert line["device"]["window_s"] == pytest.approx(reduced.window_seconds)
    assert 0 < line["device"]["busy_s"] < line["device"]["window_s"]
    breakdown = line["breakdown"]
    assert set(breakdown) == {"device_ops", "idle_gaps"}
    assert 1 <= len(breakdown["device_ops"]) <= 10 and 1 <= len(breakdown["idle_gaps"]) <= 10
    assert {name for name, _ in breakdown["idle_gaps"]} <= set(SPANS) | {"-"}
    assert list(line)[-1] == "compared"
    json.dumps(line)  # the line is plain JSON
