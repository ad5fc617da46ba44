"""The host-fed round (``benchmark/rounds/hostfed_fold.py``) at a tiny size on
the CPU, through the harness as the chip runs it: the cell's comparisons as on
the chip, the program's driver bound by dotted path, feeds that break a
guarantee caught each by its own comparison, the cohort's change between
rounds held to a python-integer sum, and the feed's three layer metrics, which
wait in ``hostfed_layers/`` for the ``benchmark`` PR that may edit the one
test that lists which metrics a trace without the program's names leaves
silent (``test_benchmark_trace_reduce.py``; PERF.md section 7). The manifest's
parametrised checks and the compile rehearsal hold ``c5-hostfed`` itself, by
its name."""

import json
import pathlib
import re
import shutil
import time
import types

import numpy as np
import pytest

import bench_tree
import cell_checks
from benchmark import harness

REPO = bench_tree.REPO
CELL, CONFIG, TRAFFIC = "c5-hostfed", "c5-w61-d100k-hostfed", "hostfed-wide"
TINY = "tiny-c5-hostfed"
DIM, ROWS, CHUNK, BLOCK_ROWS, IN_FLIGHT = 62, 48, 6, 12, 3
#: the comparisons of the line, the harness's four and the round's two
COMPARED = [
    "warmup_mismatched", "rounds_mismatched", "rounds_repeated", "compiles_in_window",
    "fed_bytes_short", "in_flight_over",
]
SHARED_METRICS = {
    "engine.input_s", "engine.rand_s", "epilogue.recombine_s", "epilogue.share_matmul_s",
    "epilogue.reconstruct_s",
}


def add_tiny_hostfed(root, name=TINY, passes=1, **traffic_changes):
    """A tiny twin of ``c5-hostfed`` as new files; every metric that lists the
    cell lists the twin too."""
    bench_tree.add_cell(
        root, name, CONFIG, TRAFFIC, DIM, ROWS, passes, CHUNK, None,
        **{"block_rows": BLOCK_ROWS, "in_flight": IN_FLIGHT, **traffic_changes},
    )
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    for metric in manifest["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return name


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = bench_tree.copy_benchmark(tmp_path_factory.mktemp("hostfed") / "copy")
    add_tiny_hostfed(root)
    return root


def run(root, workload, trace=False, seconds=0.3, seed=5):
    import jax

    return harness.run_cell(
        root, workload, seed, seconds, trace, jax.devices("cpu"),
        time.perf_counter(), out_dir=root / "out", log=lambda message: None,
    )


def session_of(root, workload, seed=5):
    import jax

    cell = harness.load_cell(root, workload)
    return harness.round_of(cell).Session(cell, seed, jax.devices("cpu"))


@pytest.mark.parametrize("seed", [5, (1 << 31) + 7])
def test_hostfed_rounds_agree_exactly_and_compare_what_the_chip_compares(tree, seed):
    line = run(tree, TINY, seed=seed)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 2
    assert list(line["compared"]) == COMPARED
    assert all(c == {"value": 0, "limit": 0} for c in line["compared"].values())
    assert set(line["metrics"]) == {"round_s", "setup_s"}
    record = json.loads((tree / "out" / f"rounds-{TINY}-seed{seed}-trace0.json").read_text())
    assert list(record["spans"]) == ["dispatch", "fold", "fetch", "epilogue", "check"]
    assert len(record["spans"]["dispatch"]) == line["attempted"]
    assert {"program", "input_to_host", "reference_on_host"} <= set(record["setup_stages_s"])


def test_the_tiny_twin_passes_the_checks_every_cell_passes(tree):
    cell_checks.check_config(tree, f"{TINY}-config")
    cell_checks.check_cell(tree, TINY)


def test_the_configuration_states_the_deployment_its_cut_and_its_guarantees():
    stated = json.loads((REPO / "benchmark/configs" / f"{CONFIG}.json").read_text())
    wide = json.loads((REPO / "benchmark/configs/c5-w61-d100k.json").read_text())
    # every width is c5-w61-d100k's, and so is every guarantee it gives
    for key in ("scheme", "dim", "participants", "chunk", "dropped_clerks"):
        assert stated[key] == wide[key], key
    for key, text in wide["guarantees"].items():
        assert stated["guarantees"][key] == text, key
    assert {"every_row_once", "rows_cross_every_round"} <= set(stated["guarantees"])
    assert list(stated["reduced"]) == ["participants"]
    for number in ("1 000 000", "125 000", "10 000", "12.5"):
        assert number in stated["reduced"]["participants"], number
    assert {"prime_modulus", "input_values", "block_rows", "in_flight", "host_arrays"} <= set(
        stated["assumed"]
    )
    traffic = json.loads((REPO / "benchmark/traffic" / f"{TRAFFIC}.json").read_text())
    assert (traffic["rows"], traffic["passes"], traffic["chunk"]) == (10_000, 1, 500)
    assert (traffic["block_rows"], traffic["in_flight"], traffic["mesh"]) == (2_500, 3, None)
    # in flight at the peak: over a quarter of the chip's memory
    in_flight_bytes = traffic["in_flight"] * traffic["block_rows"] * stated["dim"] * 8
    assert in_flight_bytes == 6_000_000_000 > cell_checks.HBM_BYTES // 4


def test_the_round_binds_the_programs_driver_and_imports_nothing_of_the_program():
    """The driver, the entry, the scheme and the counters' reader come by
    dotted path from the traffic file; the round file, the cohort's change and
    the reference are plain numpy."""
    from sda_tpu.parallel import FoldRound, sumfirst

    source = (REPO / "benchmark/rounds/hostfed_fold.py").read_text()
    assert not re.search(r"^\s*(import|from)\s+sda_tpu", source, re.M)
    assert "engine_call" not in source and "epilogue_call" not in source
    assert "sda_tpu" not in (REPO / "benchmark/reference.py").read_text().replace(
        "from ``sda_tpu``", ""
    )
    cell = harness.load_cell(REPO, CELL)
    driver, survivors, second = harness.round_of(cell).build_driver(cell)
    assert isinstance(driver, FoldRound) and driver.entry is sumfirst.value_limb_sums_chunk
    assert (driver.chunk, driver.plan.dim, driver.accumulate) == (500, 100_000, "sum")
    assert survivors == list(range(7)) and second == [0, 1, 2, 3, 4, 5, 7]


def test_the_feed_runs_one_program_c5_sumfirsts_chunk_step(tree):
    """A block is put as its chunks, so the window holds the chunk step alone,
    and it is the step of the resident round, text for text: the driver adds
    no arithmetic."""
    import jax

    devices = jax.devices("cpu")
    resident = bench_tree.add_cell(tree, "tiny-resident", "c5-w61-d100k", "sumfirst-wide",
                                   DIM, ROWS, 1, CHUNK, None)
    texts = []
    for name in (TINY, resident):
        cell = harness.load_cell(tree, name)
        ((step, args),) = harness.round_of(cell).steps(cell, devices)
        assert step.__name__ == "step"
        assert max(args, key=lambda a: a.size).shape == (CHUNK, DIM)
        texts.append(step.lower(*args).as_text())
    assert texts[0] == texts[1]
    cell = harness.load_cell(tree, TINY)
    maker, maker_args = harness.round_of(cell).input_maker(cell, devices)
    maker.lower(*maker_args)


def test_the_fed_round_gives_the_resident_rounds_clerk_sums_bit_for_bit(tree):
    """The same seed, the cohort left as it was made: ``packed_fold`` over the
    resident chunks and the feed over the host blocks hand the recipient the
    same clerk sums."""
    still = add_tiny_hostfed(tree, "tiny-still", fresh_rows_per_block=0)
    resident = bench_tree.add_cell(tree, "tiny-resident-2", "c5-w61-d100k", "sumfirst-wide",
                                   DIM, ROWS, 1, CHUNK, None)
    spans = harness.Spans()
    fed, kept = session_of(tree, still), session_of(tree, resident)
    assert np.array_equal(fed.want, kept.want)
    for index in (0, 3):
        (ok_fed, sums_fed), (ok_kept, sums_kept) = (
            s.run_round(index, spans, subsets=s.warmup_subsets) for s in (fed, kept)
        )
        assert ok_fed and ok_kept and np.array_equal(sums_fed, sums_kept)


def test_the_cohort_changes_between_rounds_and_the_reference_follows(tree):
    session = session_of(tree, TINY, seed=(1 << 31) + 11)
    assert [b.shape for b in session.blocks] == [(BLOCK_ROWS, DIM)] * (ROWS // BLOCK_ROWS)
    p = session.modulus

    def exact():
        rows = np.concatenate(session.blocks)
        return np.array([sum(int(v) for v in rows[:, j]) % p for j in range(DIM)])

    assert np.array_equal(session.want, exact())
    before = [block.copy() for block in session.blocks]
    spans = harness.Spans()
    for index in range(3):
        matched, _sums = session.run_round(index, spans)
        assert matched and np.array_equal(session.want, exact())
    changed = [int((b != a).any(axis=1).sum()) for b, a in zip(before, session.blocks)]
    assert all(1 <= rows <= 3 for rows in changed), changed
    assert session.compared() == {
        "fed_bytes_short": {"value": 0, "limit": 0}, "in_flight_over": {"value": 0, "limit": 0},
    }
    assert session.rounds_run == 3 and session.in_flight_most == IN_FLIGHT


def test_a_traced_hostfed_run_reports_the_span_metrics_and_invents_no_device_number(tree):
    line = run(tree, TINY, trace=True)
    assert line["correct"] is True
    assert bench_tree.reported(line) == {"engine.fold_s", "epilogue.s", "elems_per_s"}
    assert SHARED_METRICS <= {m["name"] for m in harness.load_cell(tree, TINY).per_layer}
    listed = {m["name"] for m in harness.load_cell(REPO, CELL).per_layer}
    assert SHARED_METRICS <= listed and "engine.layout_s" not in listed


@pytest.mark.parametrize("round_name", ["hostfed_fold", "hostfed_masked_fold"])
def test_a_traced_window_of_a_fed_round_stops_after_the_rounds_its_round_allows(tree, round_name):
    """A traced fed round leaves the profiler gigabytes of host events: its
    round caps the traced window by rounds, whatever its seconds; an untraced
    window runs on, and a round without the cap runs its traced seconds."""
    import importlib

    from benchmark.rounds import packed_fold

    module = importlib.import_module(f"benchmark.rounds.{round_name}")
    assert module.TRACE_WINDOW_ROUNDS == 2
    assert not hasattr(packed_fold, "TRACE_WINDOW_ROUNDS")
    if round_name == "hostfed_fold":
        traced = run(tree, TINY, trace=True, seconds=5.0)
        assert traced["correct"] is True and traced["attempted"] == 2
        assert run(tree, TINY, seconds=1.0)["attempted"] > 2


def test_a_feed_that_keeps_last_rounds_blocks_is_caught_by_the_aggregate(tree):
    name = add_tiny_hostfed(tree, "tiny-keeping", driver="faulty_hostfed.keeping_driver")
    line = run(tree, name)
    assert line["correct"] is False
    assert line["compared"]["warmup_mismatched"]["value"] == 0, "the first round fed its rows"
    assert line["compared"]["rounds_mismatched"]["value"] >= 1
    assert line["compared"]["fed_bytes_short"]["value"] == 0, "it counted what it did not feed"


def test_a_feed_that_drops_a_block_is_caught_by_the_byte_count_and_the_aggregate(tree):
    name = add_tiny_hostfed(tree, "tiny-dropping", driver="faulty_hostfed.dropping_driver")
    line = run(tree, name)
    assert line["correct"] is False
    rounds = line["attempted"] + 1  # the warm-up's too
    assert line["compared"]["fed_bytes_short"] == {
        "value": rounds * BLOCK_ROWS * DIM * 8, "limit": 0,
    }
    assert line["compared"]["warmup_mismatched"]["value"] == 1 and line["failed"] >= 1


def test_a_feed_over_its_bound_is_caught_by_in_flight_over(tree):
    name = add_tiny_hostfed(tree, "tiny-greedy", driver="faulty_hostfed.greedy_driver")
    line = run(tree, name)
    assert line["failed"] == 0, "every row still crossed once"
    assert line["compared"]["in_flight_over"] == {"value": 1, "limit": 0}
    assert line["correct"] is False


@pytest.mark.parametrize("changes,match", [
    ({"block_rows": 9}, "whole"),
    ({"block_rows": 10, "in_flight": 2}, "whole"),
    ({"in_flight": 0}, "in_flight"),
    ({"passes": 2}, "one pass"),
])
def test_the_hostfed_round_refuses_a_traffic_file_it_cannot_feed(tmp_path, changes, match):
    root = bench_tree.copy_benchmark(tmp_path / "copy")
    name = add_tiny_hostfed(root, "tiny-refused", **changes)
    with pytest.raises(harness.HarnessError, match=match):
        session_of(root, name)


# ---------------------------------------------------------------------------
# The feed's own layer metrics, dropped into a copy as the files a
# ``benchmark`` PR would add under ``benchmark/layers/``
# ---------------------------------------------------------------------------

LAYER_FILES = pathlib.Path(__file__).resolve().parent / "hostfed_layers"
FEED_METRICS = {"feed.put_s": "s", "feed.wait_s": "s", "feed.gb_per_s": "GB/s"}


@pytest.fixture(scope="module")
def tree_with_layers(tmp_path_factory):
    root = bench_tree.copy_benchmark(tmp_path_factory.mktemp("hostfed_layers") / "copy")
    for file in sorted(LAYER_FILES.glob("*.py")):
        shutil.copy(file, root / "benchmark/layers" / file.name)
    add_tiny_hostfed(root)
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    layers = harness.load_layers(root)
    for name in FEED_METRICS:
        module = layers[name]
        manifest["per_layer"].append({
            "name": name, "unit": module.unit,
            "better": "higher" if module.unit == "GB/s" else "lower",
            "source": "program_span", "layer": module.layer, "moves": module.moves,
            "workloads": [TINY],
        })
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root


def test_the_feed_layer_files_are_layer_files_and_pass_the_cell_checks(tree_with_layers):
    layers = harness.load_layers(tree_with_layers)
    assert sorted(p.stem for p in LAYER_FILES.glob("*.py")) == [
        "feed_gb_per_s", "feed_put_s", "feed_wait_s"
    ]
    for name, unit in FEED_METRICS.items():
        module = layers[name]
        assert (module.unit, module.layer, module.moves) == (unit, "host feed", "round_s")
        assert isinstance(module.reads_spans, tuple)
    assert layers["feed.gb_per_s"].reads_spans == ("dispatch", "fold")
    cell_checks.check_cell(tree_with_layers, TINY)


def test_a_traced_cpu_run_reports_the_feeds_rate_from_the_rounds_spans(tree_with_layers):
    """``feed.gb_per_s`` reads the round's spans, as ``engine.fold_s`` does;
    the two that read the program's spans off the profiler's clock find no
    report in a trace with no device plane, and say nothing."""
    line = run(tree_with_layers, TINY, trace=True)
    assert line["correct"] is True
    assert bench_tree.reported(line) == {"engine.fold_s", "epilogue.s", "elems_per_s", "feed.gb_per_s"}
    rate, fold = line["metrics"]["feed.gb_per_s"], line["metrics"]["engine.fold_s"]
    assert rate["unit"] == "GB/s"
    assert rate["value"] == pytest.approx(ROWS * DIM * 8 / fold["value"] / 1e9)


def test_the_feeds_span_metrics_read_the_programs_spans_a_round(tree_with_layers):
    """Their arithmetic on a made-up report, which a CPU run never reaches."""
    layers = harness.load_layers(tree_with_layers)
    context = types.SimpleNamespace(
        host_spans={"fabric.feed.put": 0.004, "fabric.feed.wait": 0.75, "fabric.reconstruct": 0.1},
        chunk_bytes=500 * 100_000 * 8, steps_per_round=20,
    )
    assert layers["feed.put_s"].reduce([], None, context) == 0.004
    assert layers["feed.wait_s"].reduce([], None, context) == 0.75
    spans = [
        harness.Span("dispatch", 1, 10.0, 11.0), harness.Span("fold", 1, 11.0, 14.0),
        harness.Span("dispatch", 2, 20.0, 20.5), harness.Span("fold", 2, 20.5, 22.0),
    ]
    assert layers["feed.gb_per_s"].reduce(spans, None, context) == pytest.approx(8.0e9 / 3.0 / 1e9)
    # nothing to read: no report, a round that never waited, no rounds
    context.host_spans = {"fabric.feed.put": 0.004}
    assert layers["feed.wait_s"].reduce([], None, context) is None
    context.host_spans = None
    for name in ("feed.put_s", "feed.wait_s"):
        assert layers[name].reduce(spans, None, context) is None
    assert layers["feed.gb_per_s"].reduce([], None, context) is None
