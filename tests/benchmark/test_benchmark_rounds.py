"""The harness end to end at a tiny size on the CPU: the four cells' rounds
agree exactly with the plain reference, faults are caught, and new cells,
configurations, traffic mixes and layer metrics are new files. The CPU
devices are handed in here; the benchmark has no option for them."""

import hashlib
import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

import bench_tree
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
    def digest(path):
        return hashlib.sha256(path.read_bytes()).hexdigest()

    originals = [
        p for p in (REPO / "benchmark").rglob("*")
        if p.is_file() and "out" not in p.parts and "__pycache__" not in p.parts
    ]
    assert originals
    for path in originals:
        copy = tree / path.relative_to(REPO)
        assert digest(copy) == digest(path), path
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
        "name, unit, layer, moves, cells = 'check.s', 's', 'reference check', 'round_s', None\n"
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


def digests(root):
    return {
        p.relative_to(root).as_posix(): hashlib.sha256(p.read_bytes()).hexdigest()
        for p in (root / "benchmark").rglob("*")
        if p.is_file() and "out" not in p.parts and "__pycache__" not in p.parts
    }


def add_masked_cell(root, name, traffic_round=None):
    """One tiny cell whose configuration states the toy round's scheme kind;
    its traffic names ``traffic_round``, or no round at all."""
    changes = {"round": traffic_round} if traffic_round else {}
    bench_tree.add_cell(root, name, *bench_tree.TINY_CELLS[0][1:], **changes)
    config_file = root / "benchmark/configs" / f"{name}-config.json"
    config = json.loads(config_file.read_text())
    config["scheme"]["kind"] = "toy_masked_packed_shamir"
    config_file.write_text(json.dumps(config))
    return name


def test_a_new_kind_of_round_is_a_new_file(tmp_path, monkeypatch):
    """What the next configuration's PR does: a round with a stage after
    reconstruct that needs state the step handed on, under a span of its own,
    comes as one module, a traffic file that names it and one ``workloads``
    entry. No file that was there is edited."""
    root = bench_tree.copy_benchmark(tmp_path / "copy")
    before = digests(root)
    shutil.copy(pathlib.Path(__file__).parent / "toy_round.py", tmp_path / "dropped_in_round.py")
    monkeypatch.syspath_prepend(str(tmp_path))
    name = add_masked_cell(root, "toy-masked", traffic_round="dropped_in_round")
    line = run(root, name)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    record = json.loads((root / "out" / f"rounds-{name}-seed5-trace0.json").read_text())
    assert list(record["spans"]) == ["dispatch", "fold", "fetch", "epilogue", "unmask", "check"]
    assert len(record["spans"]["unmask"]) == line["attempted"]
    assert all(seconds > 0 for seconds in record["spans"]["unmask"])
    cell = harness.load_cell(root, name)
    assert harness.span_names(cell)[0] == "round" and "unmask" in harness.span_names(cell)
    # the stage did work: what the clerks revealed was masked, and without the
    # state the steps handed on the round would not have matched
    import jax

    session = harness.round_of(cell).Session(cell, 5, jax.devices("cpu"), {})
    matched, _evidence = session.run_round(1, harness.Spans())
    assert matched and session.masks_total > 0 and session.masked_differs
    after = digests(root)
    assert {path: after[path] for path in before} == before, "a file that was there was edited"
    assert before == digests(REPO)
    assert set(after) - set(before) == {
        f"benchmark/configs/{name}-config.json", f"benchmark/traffic/{name}-traffic.json",
    }
    # a traced run names idle gaps and keeps host events by the round's own spans
    traced = run(root, name, trace=True)
    assert traced["correct"] is True and "epilogue.s" in traced["metrics"]


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
