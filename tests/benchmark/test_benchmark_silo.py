"""The few-fat-rows round (``benchmark/rounds/silo_fold.py``) at a tiny size on
the CPU, through the harness as the chip runs it: the program's round driver
bound by dotted path, its device epilogue and reveal (the accumulator here is
far under the size at which the driver takes that road, so the tests lower
the threshold), the whole aggregate against ``benchmark/reference_silo.py``,
faults that each comparison catches, and the cell's three layer metrics on a
recording that holds what they read. The manifest's parametrised checks and
the compile rehearsal hold ``c5-silo`` itself, by its name."""

import dataclasses
import json
import pathlib
import time

import numpy as np
import pytest

import bench_tree
import cell_checks
from benchmark import harness, reference_silo, trace_reduce

REPO = bench_tree.REPO
CELL, CONFIG, TRAFFIC = "c5-silo", "c5-w61-d109m-silo", "silo-wide"
TINY = "tiny-c5-silo"
#: 385 batches: three blocks of 128 and a last one that overlaps
DIM, ROWS, CHUNK = 1923, 8, 2
COMPARED = [
    "warmup_mismatched", "rounds_mismatched", "rounds_repeated", "compiles_in_window",
    "host_road_epilogues",
]
SPANS = ["dispatch", "fold", "epilogue", "check"]
NEW_METRICS = ("epilogue.device_s", "reveal.fetch_s", "device_epilogue_roofline")


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = bench_tree.copy_benchmark(tmp_path_factory.mktemp("silo") / "copy")
    bench_tree.add_cell(root, TINY, CONFIG, TRAFFIC, DIM, ROWS, 1, CHUNK, None, sample_batches=16)
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    for metric in manifest["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append(TINY)
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root


@pytest.fixture(autouse=True)
def device_road(monkeypatch):
    """At this size the driver would take the host road; the cell is about
    the device's. Blocks of 128 batches, so the programs loop."""
    from sda_tpu.parallel import epilogue

    monkeypatch.setattr(epilogue, "DEVICE_ROAD_BYTES", 1)
    monkeypatch.setattr(epilogue, "BLOCK_BATCHES", 128)


def run(root, trace=False, seconds=0.3, seed=5):
    import jax

    return harness.run_cell(
        root, TINY, seed, seconds, trace, jax.devices("cpu"),
        time.perf_counter(), out_dir=root / "out", log=lambda message: None,
    )


@pytest.mark.parametrize("seed", [5, (1 << 31) + 7])
def test_silo_rounds_agree_with_the_reference_on_every_column(tree, seed):
    line = run(tree, seed=seed)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 2
    assert list(line["compared"]) == COMPARED
    assert all(c == {"value": 0, "limit": 0} for c in line["compared"].values())
    assert set(line["metrics"]) == {"round_s", "setup_s"}
    record = json.loads((tree / "out" / f"rounds-{TINY}-seed{seed}-trace0.json").read_text())
    assert list(record["spans"]) == SPANS


def test_a_traced_silo_run_on_the_cpu_is_correct_and_reports_what_a_cpu_can(tree):
    line = run(tree, trace=True)
    assert line["correct"] is True
    # no device plane on the CPU: the device-trace and program-span metrics
    # are left out, the harness's own clock is not
    assert {"elems_per_s", "engine.fold_s", "epilogue.s"} <= set(line["metrics"])
    assert not set(NEW_METRICS) & set(line["metrics"])


def test_a_flipped_aggregate_element_fails_the_run(tree, monkeypatch):
    from sda_tpu.parallel.round import FoldRound

    reveal = FoldRound.reveal

    def flipped(self, clerk_sums, clerks):
        out = reveal(self, clerk_sums, clerks)
        out[DIM // 2] = (out[DIM // 2] + 1) % self.modulus
        return out

    monkeypatch.setattr(FoldRound, "reveal", flipped)
    line = run(tree)
    assert line["correct"] is False
    assert line["compared"]["warmup_mismatched"]["value"] == 1
    assert line["compared"]["rounds_mismatched"]["value"] == line["attempted"]


def test_equal_clerk_sum_samples_in_two_rounds_fail_the_run(tree, monkeypatch):
    """A round whose clerk sums repeat the round before's (no fresh share
    key): the sample the round hands the harness is what shows it."""
    from benchmark.rounds import silo_fold

    sampler = silo_fold._sampler

    def repeating(driver, batches):
        sample, first = sampler(driver, batches), []

        def same(clerk_sums):
            if not first:
                first.append(np.asarray(sample(clerk_sums)))
            return first[0]

        return same

    monkeypatch.setattr(silo_fold, "_sampler", repeating)
    line = run(tree)
    assert line["correct"] is False
    assert line["compared"]["rounds_repeated"]["value"] == line["attempted"]
    assert line["compared"]["rounds_mismatched"]["value"] == 0


def test_the_clerk_sum_sample_covers_every_clerk_and_a_share_of_the_batches(tree):
    import jax

    cell = harness.load_cell(tree, TINY)
    session = harness.round_of(cell).Session(cell, 3, jax.devices("cpu"))
    matched, sample = session.run_round(0, harness.Spans())
    assert matched and sample.shape[0] == 8 and sample.shape[1] >= 16
    assert session.acc_bytes == 2 * 2 * -(-DIM // 5) * 7 * 4


def test_a_round_on_the_host_road_fails_the_run(tree, monkeypatch):
    """The cell measures the device road: an epilogue on the host road,
    though exact, is a round the cell did not mean to measure."""
    from sda_tpu.parallel import epilogue

    monkeypatch.setattr(epilogue, "DEVICE_ROAD_BYTES", 1 << 62)
    line = run(tree)
    assert line["compared"]["rounds_mismatched"]["value"] == 0
    # every round, the warm-up with them
    assert line["compared"]["host_road_epilogues"]["value"] == line["attempted"] + 1
    assert line["correct"] is False


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------


def test_reference_silo_is_the_exact_column_sum_in_blocks(monkeypatch):
    monkeypatch.setattr(reference_silo, "BLOCK_COLUMNS", 7)
    p = (1 << 60) + 33
    rng = np.random.default_rng(1)
    rows = rng.integers(0, 1 << 60, (16, 50), dtype=np.int64)
    total = rows.astype(object).sum(axis=0)
    hi = np.array([v >> 32 for v in total], dtype=np.uint32)
    lo = np.array([v & 0xFFFFFFFF for v in total], dtype=np.uint32)
    want = reference_silo.aggregate(hi, lo, reference_silo.strided_columns(rows), p, 16)
    assert want.dtype == np.int64 and [int(v) for v in want] == [v % p for v in total]
    wrong = lo.copy()
    wrong[0] ^= 1
    with pytest.raises(AssertionError, match="disagrees with itself"):
        reference_silo.aggregate(hi, wrong, reference_silo.strided_columns(rows), p, 16)
    with pytest.raises(ValueError, match="64 bits"):
        reference_silo.aggregate(hi, lo, reference_silo.strided_columns(rows), p, 17)


def test_reference_silo_adds_rows_into_words_on_the_device():
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_enable_x64", True)
    rows = np.random.default_rng(2).integers(0, 1 << 60, (5, 40), dtype=np.int64)
    zeros = jnp.zeros((40,), jnp.uint32)
    hi, lo = jax.jit(reference_silo.add_rows)((zeros, zeros), jnp.asarray(rows))
    hi, lo = jax.jit(reference_silo.add_rows)((hi, lo), jnp.asarray(rows[:2]))
    total = rows.astype(object).sum(axis=0) + rows[:2].astype(object).sum(axis=0)
    assert [(int(h) << 32) | int(l) for h, l in zip(np.asarray(hi), np.asarray(lo))] == list(total)


# ---------------------------------------------------------------------------
# the cell's three layer metrics on a recording that holds what they read
# ---------------------------------------------------------------------------

SCOPED = pathlib.Path(__file__).parent / "recorded-trace-scopes.json"
SPAN_NAMES = ("round", "dispatch", "fold", "fetch", "epilogue", "check")


def test_the_three_layer_files_reduce_a_recording_with_the_device_epilogue():
    """The scoped recording, its busiest chip given the device epilogue's and
    reveal's scopes and the host its crossing: the three files give their
    numbers, and every other file stays as ``cell_checks`` requires."""
    import copy
    import types

    from benchmark import models_epilogue, scopes

    raw = json.loads(SCOPED.read_text())
    reduced = trace_reduce.reduce(raw, SPAN_NAMES)
    report = copy.deepcopy(scopes.split(raw, raw["paths"], SPAN_NAMES))
    busiest = report["chips"][report["busiest_chip"]]
    busiest["by_scope"].update({"fabric.epilogue": 0.0625, "fabric.reveal": 0.03125})
    report["host_spans_s"]["fabric.reveal.fetch"] = 0.28
    cell = harness.load_cell(REPO, CELL)
    spans = [harness.Span(n, 1, a * 1e-9, b * 1e-9) for n, a, b in reduced.host_spans]
    plan = types.SimpleNamespace(
        modulus=(1 << 60) + 33, input_size=5, rand_size=2, share_count=8,
        n_batches=21_896_448, dim=109_482_240,
    )
    context = harness.LayerContext(
        name=cell.name, chips=1, config=cell.config, traffic=cell.traffic,
        rounds=sum(s.name == "round" for s in spans), elements_per_round=8 * 109_482_240,
        chunk_bytes=2 * 109_482_240 * 8, acc_bytes=2 * 2 * 153_275_136 * 4, steps_per_round=4,
        plan=plan, peaks=harness.load_peaks(REPO, "TPU v5 lite"), memory_peak_bytes=15 << 30,
        log=lambda message: None, chunk_step_modules=frozenset({"jit_step"}),
        scopes=report, host_spans=report["host_spans_s"],
    )
    values = cell_checks.check_layers_on_recording(
        REPO, spans, reduced, context, cell_checks.recorded_inputs(reduced, report)
    )
    assert values["epilogue.device_s"] == 0.0625 + 0.03125
    assert values["reveal.fetch_s"] == 0.28
    least = models_epilogue.round_bytes(plan, 2, 7) / 819e9
    assert least == pytest.approx(0.00727208, rel=1e-5)
    assert values["device_epilogue_roofline"] == pytest.approx(100 * least / 0.09375)
    # one scope missing: both device metrics are silent, the crossing is not
    del busiest["by_scope"]["fabric.reveal"]
    silent = dataclasses.replace(context, scopes=report)
    layers = harness.load_layers(REPO)
    assert layers["epilogue.device_s"].reduce(spans, reduced, silent) is None
    assert layers["device_epilogue_roofline"].reduce(spans, reduced, silent) is None
    assert layers["reveal.fetch_s"].reduce(spans, reduced, silent) == 0.28
