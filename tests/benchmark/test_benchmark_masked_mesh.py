"""The masked round over a mesh (``benchmark/rounds/masked_mesh_fold.py``) at a
tiny size on four of the CPU's virtual devices, through the harness as the
chips run it: the cell's comparisons as on the chips, the program's driver
bound by dotted path with its masking scheme and its mesh, drivers that break
one of the configuration's own guarantees caught each by its own comparison,
the cell's programs compiled for a described v5e 2x2 with both kernels and a
collective in each, and the cell's own layer metrics (the recipient's
meeting, the mask stage's skew over the chips, the unmask stage). The
manifest's parametrised checks and the compile rehearsal hold
``c5-masked-x4`` itself, by its name."""

import json
import os
import re
import time
import types

import numpy as np
import pytest

import bench_tree
import cell_checks
from benchmark import harness, scopes, trace_reduce

REPO = bench_tree.REPO
CELL, CONFIG, TRAFFIC = "c5-masked-x4", "c5-w61-d100k-chacha-mesh", "masked-wide-x4"
TINY = "tiny-c5-masked-x4"
DIM, ROWS, CHUNK, CHIPS, RECIPIENT_CHUNK = 62, 16, 8, 4, 2
MESH = {"p": CHIPS, "d": 1}
#: the comparisons of the line, the harness's four and the round's six
COMPARED = [
    "warmup_mismatched", "rounds_mismatched", "rounds_repeated", "compiles_in_window",
    "unmasked_reveals", "slack_exhausted_rows", "mask_parts_mismatched",
    "seeds_repeated", "seeds_short", "unmask_chips_short",
]
SHARED_METRICS = {
    "collective.s", "collective.exposed_share", "engine.input_s", "engine.rand_s",
    "epilogue.recombine_s", "epilogue.share_matmul_s", "epilogue.reconstruct_s",
}
#: what the cell shares with both masked one-chip cells, and its own
MASKED_METRICS = {
    "mask.step_s", "unmask.stage_s", "unmask.expand_s", "chacha_rounds_roofline",
    "chacha_compact_roofline",
}
OWN_METRICS = {"mask.chip_skew", "unmask.meet_s"}
SPANS = ["dispatch", "fold", "fetch", "epilogue", "unmask", "check"]


def add_tiny_masked_mesh(root, name=TINY, **traffic_changes):
    """A tiny twin of the cell as new files, its masking block's dimension
    following its dim; every metric that lists the cell lists the twin too."""
    bench_tree.add_cell(
        root, name, CONFIG, TRAFFIC, DIM, ROWS, 1, CHUNK, MESH,
        **{"recipient_chunk": RECIPIENT_CHUNK, **traffic_changes},
    )
    config_file = root / "benchmark/configs" / f"{name}-config.json"
    stated = json.loads(config_file.read_text())
    stated["masking"]["dimension"] = DIM
    config_file.write_text(json.dumps(stated))
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    for metric in manifest["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return name


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = bench_tree.copy_benchmark(tmp_path_factory.mktemp("masked_mesh") / "copy")
    add_tiny_masked_mesh(root)
    return root


@pytest.fixture(autouse=True)
def device_combine(monkeypatch):
    """At this size the recipient would sum the masks on the host; the cell
    is about the sharded device fold."""
    from sda_tpu.crypto.masking import ChaChaMasker

    monkeypatch.setattr(ChaChaMasker, "DEVICE_COMBINE_THRESHOLD", 1)


def run(root, workload, trace=False, seconds=0.3, seed=5):
    import jax

    return harness.run_cell(
        root, workload, seed, seconds, trace, jax.devices("cpu"),
        time.perf_counter(), out_dir=root / "out", log=lambda message: None,
    )


@pytest.mark.parametrize("seed", [5, (1 << 31) + 7])
def test_masked_mesh_rounds_agree_exactly_and_compare_what_the_chips_compare(tree, seed):
    line = run(tree, TINY, seed=seed)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 2
    assert list(line["compared"]) == COMPARED
    assert all(c == {"value": 0, "limit": 0} for c in line["compared"].values())
    assert set(line["metrics"]) == {"round_s", "setup_s"}
    record = json.loads((tree / "out" / f"rounds-{TINY}-seed{seed}-trace0.json").read_text())
    assert list(record["spans"]) == SPANS
    assert len(record["spans"]["unmask"]) == line["attempted"]
    assert {"program", "input_on_device", "reference_on_host"} <= set(record["setup_stages_s"])


def test_the_tiny_twin_passes_the_checks_every_cell_passes(tree):
    cell_checks.check_config(tree, f"{TINY}-config")
    cell_checks.check_cell(tree, TINY)


def test_the_configuration_states_the_deployment_its_two_cuts_and_its_own_guarantees():
    configs = REPO / "benchmark/configs"
    stated = json.loads((configs / f"{CONFIG}.json").read_text())
    chacha = json.loads((configs / "c5-w61-d100k-chacha.json").read_text())
    assert len(stated["source"]) <= 200
    for said in ("configs[4]", "v5e-8 ICI", "crypto.rs:43-64", "full_loop.rs:43-52", "sharded"):
        assert said in stated["source"], said
    # no width differs from the masked parent's, and the masking block is its
    for key in ("scheme", "masking", "dim", "participants", "dropped_clerks"):
        assert stated[key] == chacha[key], key
    # the parent's guarantees word for word, and three of its own
    for key, text in chacha["guarantees"].items():
        assert stated["guarantees"][key] == text, key
    assert set(stated["guarantees"]) == set(chacha["guarantees"]) | {
        "chip_seeds", "every_seed_once", "recipient_sharded",
    }
    assert "no two rows of a round share a seed" in stated["guarantees"]["chip_seeds"]
    assert "one seed for every row of every chip" in stated["guarantees"]["every_seed_once"]
    assert "no chip expands another's" in stated["guarantees"]["recipient_sharded"]
    assert list(stated["reduced"]) == ["participants", "chips"]
    for number in ("1 000 000", "125 000", "10 000", "40 000"):
        assert number in stated["reduced"]["participants"], number
    assert "8" in stated["reduced"]["chips"] and "4" in stated["reduced"]["chips"]
    for key in ("prime_modulus", "input_values"):
        assert stated["assumed"][key] == chacha["assumed"][key], key
    assert {"chunk", "seeds", "recipient"} <= set(stated["assumed"])
    assert "4_chips" in stated["layout"] and "125 000" in stated["deployment"]
    assert stated["chunk"] == 2_000
    traffic = json.loads((REPO / "benchmark/traffic" / f"{TRAFFIC}.json").read_text())
    assert (traffic["rows"], traffic["passes"], traffic["chunk"]) == (40_000, 1, 2_000)
    assert (traffic["mesh"], traffic["recipient_chunk"]) == ({"p": 4, "d": 1}, 500)
    assert traffic["round"] == "benchmark.rounds.masked_mesh_fold"
    # every dotted path but the fold's is c5-hostfed-masked's
    fed = json.loads((REPO / "benchmark/traffic/hostfed-masked-wide.json").read_text())
    for key in ("driver", "engine", "sharing_scheme", "scheme_parameters", "telemetry",
                "masking_scheme"):
        assert traffic[key] == fed[key], key
    assert traffic["recipient_fold"] == "sda_tpu.ops.chacha_pallas.fold_chunk_mesh_jit"
    entry = next(w for w in cell_checks.manifest_of(REPO)["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (CONFIG, TRAFFIC, 4)
    # exactly the lists that hold the other mesh cell, or both masked one-chip
    # cells, or every cell, and its own
    for metric in cell_checks.manifest_of(REPO)["per_layer"]:
        cells = metric.get("workloads", ())
        listed = SHARED_METRICS | MASKED_METRICS | OWN_METRICS | bench_tree.SETUP_METRICS
        assert (CELL in cells) == (metric["name"] in listed), metric["name"]
        if metric["name"] in MASKED_METRICS:
            assert {"c5-masked", "c5-hostfed-masked"} <= set(cells), metric["name"]
        if metric["name"] in OWN_METRICS:
            assert cells == [CELL], metric["name"]


def test_the_round_binds_the_programs_driver_with_its_mesh_and_imports_nothing_of_the_program():
    """Driver, entry, schemes and the counters' reader come by dotted path
    from the traffic file; no adapter stands between: the driver shards the
    step, pairs the mask stage, the entry, the slack check and the masker."""
    import jax

    from sda_tpu.parallel import FoldRound, sumfirst
    from sda_tpu.protocol import ChaChaMasking

    source = (REPO / "benchmark/rounds/masked_mesh_fold.py").read_text()
    assert not re.search(r"^\s*(import|from)\s+sda_tpu", source, re.M)
    for adapter in ("engine_call", "epilogue_call", "masked_engine", "slack_check", "masker\""):
        assert adapter not in source, adapter
    traffic = json.loads((REPO / "benchmark/traffic" / f"{TRAFFIC}.json").read_text())
    assert not [key for key in traffic if key.endswith("_call")]
    cell = harness.load_cell(REPO, CELL)
    module = harness.round_of(cell)
    mesh = module.traffic_mod.make_mesh(cell.traffic, jax.devices("cpu"))
    driver, survivors, second = module.build_driver(cell, mesh)
    assert isinstance(driver, FoldRound) and driver.entry is sumfirst.value_limb_sums_chunk
    assert (driver.chunk, driver.plan.dim, driver.accumulate) == (2_000, 100_000, "sum")
    assert driver.mesh is mesh and dict(mesh.shape) == {"p": 4, "d": 1}
    masking = driver.masking
    assert isinstance(masking, ChaChaMasking)
    assert (masking.modulus, masking.dimension, masking.seed_bitsize) == (
        driver.modulus, 100_000, 128
    )
    assert survivors == list(range(7)) and second == [0, 1, 2, 3, 4, 5, 7]


def test_the_window_holds_two_sharded_programs_the_drivers_masked_step_first(tree):
    """The driver's sharded step and the recipient's sharded fold, each under
    a name of its own, a collective in each."""
    import jax

    devices = jax.devices("cpu")
    cell = harness.load_cell(tree, TINY)
    programs = harness.round_of(cell).steps(cell, devices)
    assert [jitted.__name__ for jitted, _args in programs] == ["masked_step", "_fold_chunk_mesh"]
    (_acc, chunk, _key, _index), fold_args = programs[0][1], programs[1][1]
    assert max(programs[0][1], key=lambda a: a.size) is chunk and chunk.shape == (CHUNK, DIM)
    assert chunk.sharding.shard_shape(chunk.shape) == (CHUNK // CHIPS, DIM)
    assert fold_args[0].shape == (RECIPIENT_CHUNK * CHIPS, 4) and fold_args[1] == DIM
    assert fold_args[0].sharding.shard_shape(fold_args[0].shape) == (RECIPIENT_CHUNK, 4)
    for (jitted, args), collective in zip(programs, ("all_reduce", "all_gather")):
        assert collective in jitted.lower(*args).as_text(), collective
    maker, maker_args = harness.round_of(cell).input_maker(cell, devices)
    maker.lower(*maker_args)


def test_a_session_counts_every_seed_and_every_chip_of_every_fold(tree):
    import jax

    cell = harness.load_cell(tree, TINY)
    session = harness.round_of(cell).Session(cell, (1 << 31) + 11, jax.devices("cpu"))
    spans = harness.Spans()
    for index in range(2):
        matched, _sums = session.run_round(index, spans)
        assert matched
    assert session.compared() == {
        name: {"value": 2 if name == "mask_parts_mismatched" else 0, "limit": 0}
        for name in COMPARED[4:]
    }, "no warm-up has compared the mask parts here"
    assert session.rounds_run == 2 and session.seeds_to_recipient == 2 * ROWS
    seeds, folds, fold_chips = (
        now - start for now, start in zip(session._combine(), session.combine_at_start)
    )
    per_round = ROWS // (RECIPIENT_CHUNK * CHIPS)
    assert (seeds, folds, fold_chips) == (2 * ROWS, 2 * per_round, 2 * per_round * CHIPS)
    # a step hands on, on a chip, the accumulator and its own rows' seeds and counts
    own = int(np.prod(session.driver.acc_shape)) * 8 + CHUNK // CHIPS * 5 * 4
    assert session.acc_bytes == own and session.chunk_bytes == CHUNK * DIM * 8
    matched, _sums = session.run_round(2, spans, subsets=session.warmup_subsets)
    assert matched and session.mask_parts_mismatched == 0


def faulty(tree, name, driver):
    return add_tiny_masked_mesh(tree, name, driver=f"faulty_masked_mesh.{driver}")


def own_comparisons(line, but):
    """The round's own comparisons, all but ``but``, must read 0."""
    return {name: c["value"] for name, c in line["compared"].items()
            if name in COMPARED[4:] and name != but}


def test_chips_that_draw_the_same_seeds_are_caught_by_seeds_repeated_alone(tree):
    line = run(tree, faulty(tree, "tiny-x4-same-seeds", "same_seeds_driver"))
    assert line["correct"] is False
    assert line["failed"] == 0, "equal masks cancel as well as distinct ones"
    rounds = line["attempted"] + 1  # the warm-up's too
    assert line["compared"]["seeds_repeated"] == {
        "value": rounds * (ROWS - ROWS // CHIPS), "limit": 0,
    }
    assert not any(own_comparisons(line, "seeds_repeated").values())
    assert line["compared"]["warmup_mismatched"]["value"] == 0


def test_a_seed_dropped_before_the_combine_is_caught_by_seeds_short_and_the_aggregate(tree):
    line = run(tree, faulty(tree, "tiny-x4-dropping", "seed_dropping_driver"))
    assert line["correct"] is False
    rounds = line["attempted"] + 1
    assert line["compared"]["seeds_short"] == {"value": rounds, "limit": 0}
    assert not any(own_comparisons(line, "seeds_short").values())
    assert line["compared"]["rounds_mismatched"]["value"] == line["attempted"]
    assert line["compared"]["warmup_mismatched"]["value"] == 1


def test_a_combine_on_one_chip_is_caught_by_unmask_chips_short_alone(tree):
    line = run(tree, faulty(tree, "tiny-x4-one-chip", "one_chip_combine_driver"))
    assert line["correct"] is False
    assert line["failed"] == 0, "one chip expands the same masks"
    rounds = line["attempted"] + 1
    folds = rounds * ROWS // RECIPIENT_CHUNK  # a chip's, every one on the first chip
    assert line["compared"]["unmask_chips_short"] == {
        "value": folds * (CHIPS - 1), "limit": 0,
    }
    assert not any(own_comparisons(line, "unmask_chips_short").values())
    assert line["compared"]["warmup_mismatched"]["value"] == 0


@pytest.mark.parametrize("changes,match", [
    ({"mesh": {"p": 2, "d": 2}}, "d = 1"),
    ({"passes": 2}, "one pass"),
    ({"recipient_chunk": 3}, "whole"),
    ({"masking_scheme": None}, "masking_scheme"),
])
def test_the_round_refuses_a_traffic_file_it_cannot_run(tmp_path, changes, match):
    import jax

    root = bench_tree.copy_benchmark(tmp_path / "copy")
    name = add_tiny_masked_mesh(root, "tiny-x4-refused")
    file = root / "benchmark/traffic" / f"{name}-traffic.json"
    traffic = {**json.loads(file.read_text()), **changes}
    file.write_text(json.dumps({k: v for k, v in traffic.items() if v is not None}))
    with pytest.raises((harness.HarnessError, ValueError), match=match):
        cell = harness.load_cell(root, name)
        harness.round_of(cell).Session(cell, 5, jax.devices("cpu"))


# ---------------------------------------------------------------------------
# The cell's programs for the chips they run on: described, not attached
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # whatever keeps the TPU's compiler from loading
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def quiet_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one: keep the cache off around these."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)


def test_both_programs_compile_for_a_v5e_2x2_with_both_kernels_and_a_collective_each(
    topo, quiet_cache
):
    """What ``cell_checks.check_programs_compile_and_fit`` holds of every cell,
    and of this one more: both Pallas kernels are in the sharded step's text
    and in the sharded fold's, and each program exchanges something."""
    cell = harness.load_cell(REPO, CELL)
    programs = harness.round_of(cell).steps(cell, list(topo.devices))
    resident = cell_checks._resident_bytes(cell, programs)
    assert resident == 10_000 * 100_000 * 8, "a chip's rows"
    names, largest = [], 0
    for jitted, args in programs:
        compiled = jitted.lower(*args).compile()
        text = compiled.as_text()
        assert text.count("tpu_custom_call") >= 2, "the rounds kernel and the compaction kernel"
        for kernel in ("chacha_rounds", "chacha_compact"):
            assert kernel in text, kernel
        assert trace_reduce.COLLECTIVE.search(text), "nothing crosses the chips"
        memory = compiled.memory_analysis()
        largest = max(largest, memory.temp_size_in_bytes + memory.output_size_in_bytes)
        names.append(next(iter(scopes.op_paths(text))).split("/", 1)[0])
    assert names == ["jit_masked_step", "jit__fold_chunk_mesh"]
    assert resident + largest < cell_checks.HBM_BYTES


#: sha256 of the lowered text of the first of ``steps(cell, devices)`` for a
#: described v5e 2x2, its first sixteen digits: the programs of the standing
#: cells that a round built without a mesh, or the sharded entry, must leave as
#: they are, text for text (PERF.md section 6, PR 30; a step with a Pallas
#: kernel in it has none to hold: its module carries file paths and lines)
HELD_DIGESTS = {
    "c5-sumfirst": "14a1a6727a655e76", "c5-hostfed": "14a1a6727a655e76",
    "c5-sumfirst-x4": "20920bd8b23b2c9f", "c4-sumfirst": "3f4e65868b816edd",
}


def lowered_step(name, devices) -> str:
    cell = harness.load_cell(REPO, name)
    jitted, args = harness.round_of(cell).steps(cell, list(devices))[0]
    return jitted.lower(*args).as_text()


@pytest.mark.parametrize("name", sorted(HELD_DIGESTS))
def test_the_standing_cells_steps_lower_to_the_text_they_had(name, topo):
    import hashlib

    text = lowered_step(name, topo.devices)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == HELD_DIGESTS[name]


def test_the_drivers_unmasked_mesh_step_is_the_sharded_cells_program(topo):
    """``fold_round(..., mesh=)`` with no masking scheme builds, for the
    sum-first entry, the program ``c5-sumfirst-x4`` runs, text for text: that
    cell's step by another road."""
    from sda_tpu.parallel import fold_round, sumfirst

    cell = harness.load_cell(REPO, "c5-sumfirst-x4")
    module = harness.round_of(cell)
    devices = list(topo.devices)
    _jitted, args = module.steps(cell, devices)[0]
    mesh = module.traffic_mod.make_mesh(cell.traffic, devices)
    scheme = module.build_program(cell, mesh).scheme
    driver = fold_round(
        scheme, cell.dim, sumfirst.value_limb_sums_chunk, cell.traffic.chunk, mesh=mesh
    )
    assert driver.step.lower(*args).as_text() == lowered_step("c5-sumfirst-x4", devices)


# ---------------------------------------------------------------------------
# The cell's own layer metrics, under ``benchmark/layers/``
# ---------------------------------------------------------------------------

HOOKED = {
    "unmask.stage_s": ("s", "recipient unmask", "program_span"),
    "unmask.meet_s": ("s", "recipient unmask", "device_trace"),
    "mask.chip_skew": ("ratio", "mask stage", "device_trace"),
}


def test_the_staged_layer_files_are_layer_files_and_pass_the_cell_checks(tree):
    layers = harness.load_layers(REPO)
    listed = {m["name"]: m for m in cell_checks.manifest_of(REPO)["per_layer"]}
    for name, (unit, layer, source) in HOOKED.items():
        module = layers[name]
        assert (module.unit, module.layer, module.moves) == (unit, layer, "round_s")
        assert listed[name]["source"] == source and CELL in listed[name]["workloads"]
        assert isinstance(module.reads_spans, tuple)
        text = (REPO / "benchmark/layers" / f"{name.replace('.', '_')}.py").read_text()
        assert "import sda_tpu" not in text and "from sda_tpu" not in text
    assert layers["unmask.stage_s"].reads_spans == ("unmask",)
    assert layers["unmask.meet_s"].reads == ("scope:fabric.unmask/meet",)
    assert layers["mask.chip_skew"].reads == ("scope:fabric.mask",)
    cell_checks.check_cell(tree, TINY)


def test_a_traced_cpu_run_reports_the_unmask_stage_and_invents_no_device_number(tree):
    """The span metrics and the one of its own that reads a span; of those
    that the cell shares with its neighbours and of its own that read the
    device, nothing: a CPU trace has no device plane."""
    line = run(tree, TINY, trace=True)
    assert line["correct"] is True
    assert bench_tree.reported(line) == {
        "engine.fold_s", "epilogue.s", "elems_per_s", "unmask.stage_s",
    }
    reported = {m["name"] for m in harness.load_cell(tree, TINY).per_layer}
    assert SHARED_METRICS | MASKED_METRICS | OWN_METRICS <= reported
    record = json.loads((tree / "out" / f"rounds-{TINY}-seed5-trace1.json").read_text())
    stage = line["metrics"]["unmask.stage_s"]["value"]
    assert min(record["spans"]["unmask"]) <= stage <= max(record["spans"]["unmask"])


def test_the_meeting_and_the_skew_read_every_chip_of_a_scope_report():
    """Their arithmetic on a made-up scope report, which a CPU run never
    reaches."""
    layers = harness.load_layers(REPO)
    meet, skew = layers["unmask.meet_s"], layers["mask.chip_skew"]

    def chip(mask, met):
        by_path = {"fabric.mask/expand": mask, "fabric.unmask/expand": 0.3}
        if met is not None:
            by_path["fabric.unmask/meet"] = met
        return {"by_scope": {"fabric.mask": mask, "fabric.unmask": 0.3}, "by_path": by_path}

    report = {"busiest_chip": "c0", "chips": {
        "c0": chip(0.40, 0.002), "c1": chip(0.36, 0.005), "c2": chip(0.38, 0.001),
        "c3": chip(0.32, 0.003),
    }}
    context = types.SimpleNamespace(scopes=report)
    trace = object()
    assert meet.reduce([], trace, context) == 0.005, "the chip with most, not the busiest"
    assert skew.reduce([], trace, context) == pytest.approx(0.40 / 0.32)
    # nothing to read: no device plane, no join, no such scope (the parent's
    # fold, a one-chip fold), one chip, a chip that masked nothing
    assert meet.reduce([], None, context) is None and skew.reduce([], None, context) is None
    context.scopes = None
    assert meet.reduce([], trace, context) is None and skew.reduce([], trace, context) is None
    context.scopes = {"busiest_chip": "c0", "chips": {"c0": chip(0.4, None), "c1": chip(0.0, None)}}
    assert meet.reduce([], trace, context) is None and skew.reduce([], trace, context) is None
    context.scopes = {"busiest_chip": "c0", "chips": {"c0": chip(0.4, 0.002)}}
    assert meet.reduce([], trace, context) == 0.002 and skew.reduce([], trace, context) is None
    spans = [harness.Span("unmask", 1, 11.4, 12.6), harness.Span("unmask", 2, 21.6, 22.6)]
    assert layers["unmask.stage_s"].reduce(spans, trace, context) == pytest.approx(1.1)
    assert layers["unmask.stage_s"].reduce([], trace, context) is None
