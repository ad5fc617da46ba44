"""The masked host-fed round (``benchmark/rounds/hostfed_masked_fold.py``) at a
tiny size on the CPU, through the harness as the chip runs it: the cell's
comparisons as on the chip, the program's masked driver bound by dotted path,
feeds and steps that break a guarantee caught each by its own comparison, the
same clerk sums as the resident masked round, and the cell's three layer
metrics, which wait in ``hostfed_masked_layers/`` for the ``benchmark`` PR that
may edit the one test that lists which metrics a trace without the program's
names leaves silent (``test_benchmark_trace_reduce.py``; PERF.md section 7).
The manifest's parametrised checks and the compile rehearsal hold
``c5-hostfed-masked`` itself, by its name."""

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
CELL, CONFIG, TRAFFIC = "c5-hostfed-masked", "c5-w61-d100k-chacha-hostfed", "hostfed-masked-wide"
TINY = "tiny-c5-hostfed-masked"
DIM, ROWS, CHUNK, BLOCK_ROWS, IN_FLIGHT = 62, 48, 6, 12, 3
#: the comparisons of the line, the harness's four and the round's six
COMPARED = [
    "warmup_mismatched", "rounds_mismatched", "rounds_repeated", "compiles_in_window",
    "fed_bytes_short", "in_flight_over", "unmasked_reveals", "slack_exhausted_rows",
    "mask_parts_mismatched", "seeds_short",
]
SHARED_METRICS = {
    "engine.input_s", "engine.rand_s", "epilogue.recombine_s", "epilogue.share_matmul_s",
    "epilogue.reconstruct_s",
}
SPANS = ["dispatch", "fold", "fetch", "epilogue", "unmask", "check"]


def add_twin(root, name, cell, config, traffic, **traffic_changes):
    """A tiny twin of a masked ``cell`` as new files, at this file's sizes, its
    masking block's dimension following its dim; every metric that lists the
    cell lists the twin too."""
    bench_tree.add_cell(
        root, name, config, traffic, DIM, ROWS, 1, CHUNK, None,
        **{"recipient_chunk": CHUNK, **traffic_changes},
    )
    config_file = root / "benchmark/configs" / f"{name}-config.json"
    stated = json.loads(config_file.read_text())
    stated["masking"]["dimension"] = DIM
    config_file.write_text(json.dumps(stated))
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    for metric in manifest["per_layer"]:
        if cell in metric.get("workloads", ()):
            metric["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return name


def add_tiny_hostfed_masked(root, name=TINY, **traffic_changes):
    return add_twin(
        root, name, CELL, CONFIG, TRAFFIC,
        **{"block_rows": BLOCK_ROWS, "in_flight": IN_FLIGHT, **traffic_changes},
    )


def add_tiny_resident_masked(root, name):
    """``c5-masked``'s twin over the same rows: the resident masked round."""
    return add_twin(root, name, "c5-masked", "c5-w61-d100k-chacha", "masked-wide")


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = bench_tree.copy_benchmark(tmp_path_factory.mktemp("hostfed_masked") / "copy")
    add_tiny_hostfed_masked(root)
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


def session_of(root, workload, seed=5):
    import jax

    cell = harness.load_cell(root, workload)
    return harness.round_of(cell).Session(cell, seed, jax.devices("cpu"))


@pytest.mark.parametrize("seed", [5, (1 << 31) + 7])
def test_masked_hostfed_rounds_agree_exactly_and_compare_what_the_chip_compares(tree, seed):
    line = run(tree, TINY, seed=seed)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 2
    assert list(line["compared"]) == COMPARED
    assert all(c == {"value": 0, "limit": 0} for c in line["compared"].values())
    assert set(line["metrics"]) == {"round_s", "setup_s"}
    record = json.loads((tree / "out" / f"rounds-{TINY}-seed{seed}-trace0.json").read_text())
    assert list(record["spans"]) == SPANS
    assert len(record["spans"]["unmask"]) == line["attempted"]
    assert {"program", "input_to_host", "reference_on_host"} <= set(record["setup_stages_s"])


def test_the_tiny_twin_passes_the_checks_every_cell_passes(tree):
    cell_checks.check_config(tree, f"{TINY}-config")
    cell_checks.check_cell(tree, TINY)


def test_the_configuration_states_the_deployment_its_cut_and_both_parents_guarantees():
    configs = REPO / "benchmark/configs"
    stated = json.loads((configs / f"{CONFIG}.json").read_text())
    chacha = json.loads((configs / "c5-w61-d100k-chacha.json").read_text())
    hostfed = json.loads((configs / "c5-w61-d100k-hostfed.json").read_text())
    assert len(stated["source"]) <= 200 and "crypto.rs:43-64" in stated["source"]
    # no width differs from the masked parent's, and the masking block is its
    for key in ("scheme", "masking", "dim", "participants", "chunk", "dropped_clerks"):
        assert stated[key] == chacha[key], key
    assert stated["scheme"] == hostfed["scheme"]
    # the union of the two parents' guarantees, word for word, and one more
    for parent in (chacha, hostfed):
        for key, text in parent["guarantees"].items():
            assert stated["guarantees"][key] == text, key
    assert set(stated["guarantees"]) == (
        set(chacha["guarantees"]) | set(hostfed["guarantees"]) | {"every_seed_once"}
    )
    assert "exactly one seed for every row" in stated["guarantees"]["every_seed_once"]
    assert list(stated["reduced"]) == ["participants"]
    for number in ("1 000 000", "125 000", "10 000", "12.5"):
        assert number in stated["reduced"]["participants"], number
    assert {"prime_modulus", "input_values", "block_rows", "in_flight", "host_arrays",
            "fresh_rows", "seeds"} <= set(stated["assumed"])
    assert "1_chip" in stated["layout"] and "deployment" in stated
    traffic = json.loads((REPO / "benchmark/traffic" / f"{TRAFFIC}.json").read_text())
    assert (traffic["rows"], traffic["passes"], traffic["chunk"]) == (10_000, 1, 500)
    assert (traffic["block_rows"], traffic["in_flight"], traffic["mesh"]) == (2_500, 3, None)
    assert (traffic["fresh_rows_per_block"], traffic["recipient_chunk"]) == (1, 500)
    assert traffic["round"] == "benchmark.rounds.hostfed_masked_fold"
    # every dotted path but the masking scheme's and the fold's is c5-hostfed's
    fed = json.loads((REPO / "benchmark/traffic/hostfed-wide.json").read_text())
    for key in ("driver", "engine", "sharing_scheme", "scheme_parameters", "telemetry"):
        assert traffic[key] == fed[key], key
    assert traffic["masking_scheme"] == "sda_tpu.protocol.ChaChaMasking"
    assert traffic["recipient_fold"] == "sda_tpu.ops.chacha_pallas.fold_chunk_jit"
    entry = next(w for w in cell_checks.manifest_of(REPO)["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (CONFIG, TRAFFIC, 1)


def test_the_round_binds_the_programs_masked_driver_and_imports_nothing_of_the_program():
    """Driver, entry, schemes and the counters' reader come by dotted path
    from the traffic file; no adapter stands between: the driver pairs the
    mask stage, the entry, the slack check and the masker."""
    from sda_tpu.parallel import FoldRound, sumfirst
    from sda_tpu.protocol import ChaChaMasking

    source = (REPO / "benchmark/rounds/hostfed_masked_fold.py").read_text()
    assert not re.search(r"^\s*(import|from)\s+sda_tpu", source, re.M)
    for adapter in ("engine_call", "epilogue_call", "masked_engine", "slack_check", "masker\""):
        assert adapter not in source, adapter
    traffic = json.loads((REPO / "benchmark/traffic" / f"{TRAFFIC}.json").read_text())
    assert not [key for key in traffic if key.endswith("_call")]
    cell = harness.load_cell(REPO, CELL)
    driver, survivors, second = harness.round_of(cell).build_driver(cell)
    assert isinstance(driver, FoldRound) and driver.entry is sumfirst.value_limb_sums_chunk
    assert (driver.chunk, driver.plan.dim, driver.accumulate) == (500, 100_000, "sum")
    assert isinstance(driver.masking, ChaChaMasking)
    masking = driver.masking
    assert (masking.modulus, masking.dimension, masking.seed_bitsize) == (
        driver.modulus, 100_000, 128
    )
    assert survivors == list(range(7)) and second == [0, 1, 2, 3, 4, 5, 7]


def test_the_window_holds_two_programs_the_drivers_masked_step_first(tree):
    """The feed's step and the recipient's fold, under the names ``c5-masked``'s
    trace has them by; the step is ``c5-masked``'s masked step, text for text."""
    import jax

    devices = jax.devices("cpu")
    resident = add_tiny_resident_masked(tree, "tiny-resident-masked")
    texts = {}
    for name in (TINY, resident):
        cell = harness.load_cell(tree, name)
        programs = harness.round_of(cell).steps(cell, devices)
        assert [jitted.__name__ for jitted, _args in programs] == ["masked_step", "_fold_chunk"]
        (_acc, chunk, _key, _index), fold_args = programs[0][1], programs[1][1]
        assert max(programs[0][1], key=lambda a: a.size) is chunk and chunk.shape == (CHUNK, DIM)
        assert fold_args[0].shape == (CHUNK, 4) and fold_args[1] == DIM
        texts[name] = [jitted.lower(*args).as_text() for jitted, args in programs]
    assert texts[TINY] == texts[resident]
    cell = harness.load_cell(tree, TINY)
    maker, maker_args = harness.round_of(cell).input_maker(cell, devices)
    maker.lower(*maker_args)


def test_the_fed_masked_round_gives_the_resident_masked_rounds_clerk_sums_bit_for_bit(tree):
    """The same seed, the cohort left as it was made: ``masked_fold`` over the
    resident chunks and the masked feed over the host blocks draw the same
    seeds and hand the recipient the same clerk sums."""
    still = add_tiny_hostfed_masked(tree, "tiny-still-masked", fresh_rows_per_block=0)
    resident = add_tiny_resident_masked(tree, "tiny-resident-masked-2")
    spans = harness.Spans()
    fed, kept = session_of(tree, still), session_of(tree, resident)
    assert np.array_equal(fed.want, kept.want)
    for index in (0, 3):
        (ok_fed, sums_fed), (ok_kept, sums_kept) = (
            s.run_round(index, spans, subsets=s.warmup_subsets) for s in (fed, kept)
        )
        assert ok_fed and ok_kept and np.array_equal(sums_fed, sums_kept)
    assert fed.compared()["seeds_short"] == {"value": 0, "limit": 0}
    assert fed.seeds_to_recipient == 2 * ROWS and fed.mask_parts_mismatched == 0


def test_the_cohort_changes_between_rounds_and_every_round_hands_on_its_own_seeds(tree):
    session = session_of(tree, TINY, seed=(1 << 31) + 11)
    p = session.modulus

    def exact():
        rows = np.concatenate(session.blocks)
        return np.array([sum(int(v) for v in rows[:, j]) % p for j in range(DIM)])

    spans = harness.Spans()
    for index in range(3):
        matched, _sums = session.run_round(index, spans)
        assert matched and np.array_equal(session.want, exact())
    assert session.compared() == {
        name: {"value": 2 if name == "mask_parts_mismatched" else 0, "limit": 0}
        for name in COMPARED[4:]
    }, "no warm-up has compared the mask parts here"
    assert session.rounds_run == 3 and session.seeds_to_recipient == 3 * ROWS
    assert session.in_flight_most == IN_FLIGHT
    # a step hands on its seeds and counts beside the accumulator
    assert session.acc_bytes == int(np.prod(session.driver.acc_shape)) * 8 + CHUNK * 5 * 4


def test_a_traced_run_reports_the_span_metrics_and_invents_no_device_number(tree):
    line = run(tree, TINY, trace=True)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"engine.fold_s", "epilogue.s", "elems_per_s"}
    assert SHARED_METRICS <= {m["name"] for m in harness.load_cell(tree, TINY).per_layer}
    # exactly the lists that held both parents' cells gained the new one
    for metric in cell_checks.manifest_of(REPO)["per_layer"]:
        cells = metric.get("workloads", ())
        assert (CELL in cells) == ("c5-masked" in cells and "c5-hostfed" in cells), metric["name"]
        assert (CELL in cells) == (metric["name"] in SHARED_METRICS)


def faulty(tree, name, driver):
    return add_tiny_hostfed_masked(tree, name, driver=f"faulty_hostfed_masked.{driver}")


def test_a_feed_that_keeps_last_rounds_blocks_is_caught_by_the_aggregate(tree):
    line = run(tree, faulty(tree, "tiny-m-keeping", "keeping_driver"))
    assert line["correct"] is False
    assert line["compared"]["warmup_mismatched"]["value"] == 0, "the first round fed its rows"
    assert line["compared"]["rounds_mismatched"]["value"] >= 1
    for counted in ("fed_bytes_short", "seeds_short"):
        assert line["compared"][counted]["value"] == 0, "it counted what it did not feed"


def test_a_feed_that_drops_a_block_is_caught_by_the_byte_count(tree):
    line = run(tree, faulty(tree, "tiny-m-dropping", "dropping_driver"))
    assert line["correct"] is False
    rounds = line["attempted"] + 1  # the warm-up's too
    assert line["compared"]["fed_bytes_short"] == {
        "value": rounds * BLOCK_ROWS * DIM * 8, "limit": 0,
    }
    assert line["compared"]["seeds_short"] == {"value": rounds * BLOCK_ROWS, "limit": 0}
    assert line["compared"]["warmup_mismatched"]["value"] == 1 and line["failed"] >= 1


def test_a_feed_that_loses_a_steps_seeds_is_caught_by_seeds_short_and_the_aggregate(tree):
    line = run(tree, faulty(tree, "tiny-m-losing", "seed_losing_driver"))
    assert line["correct"] is False
    rounds = line["attempted"] + 1
    assert line["compared"]["seeds_short"] == {"value": rounds * CHUNK, "limit": 0}
    assert line["compared"]["fed_bytes_short"]["value"] == 0, "every row crossed"
    assert line["compared"]["rounds_mismatched"]["value"] == line["attempted"]
    assert line["compared"]["warmup_mismatched"]["value"] == 1


def test_a_mask_stage_that_adds_nothing_is_caught_by_unmasked_reveals(tree):
    line = run(tree, faulty(tree, "tiny-m-unmasked", "unmasking_driver"))
    assert line["correct"] is False
    reveals = line["compared"]["unmasked_reveals"]
    assert reveals["limit"] == 0 and reveals["value"] == line["attempted"] + 1
    assert line["compared"]["seeds_short"]["value"] == 0


def test_counts_under_dim_are_caught_by_slack_exhausted_rows(tree):
    line = run(tree, faulty(tree, "tiny-m-short", "short_window_driver"))
    assert line["failed"] == 0, "the masks were whole: only the counts say otherwise"
    assert line["compared"]["slack_exhausted_rows"] == {
        "value": (line["attempted"] + 1) * ROWS, "limit": 0,
    }
    assert line["correct"] is False


@pytest.mark.parametrize("changes,match", [
    ({"block_rows": 9}, "whole"),
    ({"in_flight": 0}, "in_flight"),
    ({"passes": 2}, "one pass"),
    ({"masking_scheme": None}, "masking_scheme"),
])
def test_the_round_refuses_a_traffic_file_it_cannot_feed(tmp_path, changes, match):
    root = bench_tree.copy_benchmark(tmp_path / "copy")
    name = add_tiny_hostfed_masked(root, "tiny-m-refused")
    file = root / "benchmark/traffic" / f"{name}-traffic.json"
    traffic = {**json.loads(file.read_text()), **changes}
    file.write_text(json.dumps({k: v for k, v in traffic.items() if v is not None or k == "mesh"}))
    with pytest.raises((harness.HarnessError, ValueError), match=match):
        session_of(root, name)


def test_the_round_refuses_a_configuration_that_masks_nothing(tmp_path):
    root = bench_tree.copy_benchmark(tmp_path / "copy")
    name = bench_tree.add_cell(
        root, "no-masking-fed", "c5-w61-d100k-hostfed", TRAFFIC, DIM, ROWS, 1, CHUNK, None,
        block_rows=BLOCK_ROWS,
    )
    with pytest.raises(harness.HarnessError, match="masking"):
        session_of(root, name)


# ---------------------------------------------------------------------------
# The cell's own layer metrics, dropped into a copy as the files a
# ``benchmark`` PR would add under ``benchmark/layers/``
# ---------------------------------------------------------------------------

LAYER_FILES = pathlib.Path(__file__).resolve().parent / "hostfed_masked_layers"
STAGED = {
    "feed.exposed_s": ("s", "host feed", "device_trace"),
    "feed.seeds_per_round": ("seeds", "host feed", "program_counter"),
    "unmask.stage_s": ("s", "recipient unmask", "program_span"),
}


@pytest.fixture(scope="module")
def tree_with_layers(tmp_path_factory):
    root = bench_tree.copy_benchmark(tmp_path_factory.mktemp("hostfed_masked_layers") / "copy")
    for file in sorted(LAYER_FILES.glob("*.py")):
        shutil.copy(file, root / "benchmark/layers" / file.name)
    add_tiny_hostfed_masked(root)
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    layers = harness.load_layers(root)
    for name, (unit, _layer, source) in STAGED.items():
        module = layers[name]
        manifest["per_layer"].append({
            "name": name, "unit": unit, "better": "higher" if unit == "seeds" else "lower",
            "source": source, "layer": module.layer, "moves": module.moves, "workloads": [TINY],
        })
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root


def test_the_staged_layer_files_are_layer_files_and_pass_the_cell_checks(tree_with_layers):
    layers = harness.load_layers(tree_with_layers)
    assert sorted(p.stem for p in LAYER_FILES.glob("*.py")) == [
        "feed_exposed_s", "feed_seeds_per_round", "unmask_stage_s"
    ]
    for name, (unit, layer, _source) in STAGED.items():
        module = layers[name]
        assert (module.unit, module.layer, module.moves) == (unit, layer, "round_s")
        assert isinstance(module.reads_spans, tuple)
        source = (LAYER_FILES / f"{name.replace('.', '_')}.py").read_text()
        assert "import sda_tpu" not in source and "from sda_tpu" not in source
    assert layers["feed.exposed_s"].reads_spans == ("dispatch", "fold")
    assert layers["unmask.stage_s"].reads_spans == ("unmask",)
    cell_checks.check_cell(tree_with_layers, TINY)


def test_a_traced_cpu_run_reports_the_seeds_a_round_and_the_unmask_stage(tree_with_layers):
    """The counter and the round's span need no device plane; the exposed
    share of the fold does, and says nothing here."""
    from sda_tpu import telemetry

    telemetry.reset()
    line = run(tree_with_layers, TINY, trace=True)
    assert line["correct"] is True
    assert set(line["metrics"]) == {
        "engine.fold_s", "epilogue.s", "elems_per_s", "feed.seeds_per_round", "unmask.stage_s",
    }
    assert line["metrics"]["feed.seeds_per_round"] == {"value": float(ROWS), "unit": "seeds"}
    record = json.loads(
        (tree_with_layers / "out" / f"rounds-{TINY}-seed5-trace1.json").read_text()
    )
    stage = line["metrics"]["unmask.stage_s"]["value"]
    assert min(record["spans"]["unmask"]) <= stage <= max(record["spans"]["unmask"])


def test_the_exposed_share_is_the_fold_less_the_steps_own_device_seconds(tree_with_layers):
    """Its arithmetic on a made-up trace, which a CPU run never reaches."""
    from sda_tpu import telemetry

    layers = harness.load_layers(tree_with_layers)
    spans = [
        harness.Span("dispatch", 1, 10.0, 10.4), harness.Span("fold", 1, 10.4, 11.3),
        harness.Span("dispatch", 2, 20.0, 20.5), harness.Span("fold", 2, 20.5, 21.5),
        harness.Span("unmask", 1, 11.4, 12.6), harness.Span("unmask", 2, 21.6, 22.6),
    ]
    asked = []

    def busy(modules=None):
        asked.append(modules)
        return 2.4  # the window's two rounds

    trace = types.SimpleNamespace(max_busy_seconds=busy)
    context = types.SimpleNamespace(rounds=2, chunk_step_modules=frozenset({"jit_masked_step"}))
    assert layers["feed.exposed_s"].reduce(spans, trace, context) == pytest.approx(1.4 - 1.2)
    assert asked == [frozenset({"jit_masked_step"})], "the chunk step's program alone"
    assert layers["unmask.stage_s"].reduce(spans, trace, context) == pytest.approx(1.1)
    # a fold the chip filled: never under 0
    trace.max_busy_seconds = lambda modules=None: 3.2
    assert layers["feed.exposed_s"].reduce(spans, trace, context) == 0.0
    # nothing to read: no device plane, no step named, a step the trace lacks, no rounds
    assert layers["feed.exposed_s"].reduce(spans, None, context) is None
    context.chunk_step_modules = None
    assert layers["feed.exposed_s"].reduce(spans, trace, context) is None
    context.chunk_step_modules = frozenset({"jit_not_there"})
    trace.max_busy_seconds = lambda modules=None: 0.0
    assert layers["feed.exposed_s"].reduce(spans, trace, context) is None
    assert layers["unmask.stage_s"].reduce([], trace, context) is None
    # the counter: nothing from a program that has none, or that masked nothing
    telemetry.reset()
    assert layers["feed.seeds_per_round"].reduce(spans, None, context) is None
    telemetry.counter("sda_fabric_fed_seeds_total").inc(0)
    assert layers["feed.seeds_per_round"].reduce(spans, None, context) is None
    telemetry.counter("sda_fabric_fed_seeds_total").inc(3 * 48)
    assert layers["feed.seeds_per_round"].reduce(spans, None, context) == 48.0
    telemetry.reset()
