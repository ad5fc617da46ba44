"""``benchmark/scopes.py``, the reader that turns a trace into seconds by the
program's own names: on hand-made text and a hand-made trace whose numbers
can be worked out on paper, on a small trace recorded on the chip
(``recorded-trace-scopes.json``, made by ``record_scopes_trace.py``), and the
join of operation names with compiled text on the CPU."""

import json
import pathlib
import time

import pytest

import bench_tree
from benchmark import harness, scopes
from benchmark.rounds import packed_fold

#: the four cells' spans: the harness's and those of their round
SPANS = ("round", *packed_fold.span_names)
HERE = pathlib.Path(__file__).resolve().parent
US = 1000.0  # nanoseconds

#: a compiled module in miniature: a fusion that carries its root's op_name
#: though most of what is fused into it is the draw, a fusion whose root the
#: compiler made (no metadata of its own), a loop the compiler made (none
#: anywhere), and the harness's own add
HLO = '''HloModule jit_step, is_scheduled=true, entry_computation_layout={()->s32[4]{0}}

%fused_computation.1 (p.1: u32[4]) -> s32[4] {
  %p.1 = u32[4]{0} parameter(0)
  %rem.1 = u32[4]{0} remainder(%p.1, %p.1), metadata={op_name="jit(step)/fabric.rand/draw/jit(remainder)/rem"}
  %xor.1 = u32[4]{0} xor(%rem.1, %p.1), metadata={op_name="jit(step)/fabric.rand/draw/xor"}
  %add.1 = u32[4]{0} add(%xor.1, %p.1), metadata={op_name="jit(step)/fabric.rand/draw/add"}
  ROOT %reduce.1 = s32[4]{0} convert(%add.1), metadata={op_name="jit(step)/fabric.rand/limb_sum/reduce_sum"}
}

%fused_computation.2 (p.2: u32[4]) -> s32[4] {
  %p.2 = u32[4]{0} parameter(0)
  %rem.2 = u32[4]{0} remainder(%p.2, %p.2), metadata={op_name="jit(step)/fabric.rand/draw/jit(remainder)/rem"}
  %or.2 = u32[4]{0} or(%rem.2, %p.2), metadata={op_name="jit(step)/fabric.rand/draw/or"}
  %convert.2 = s32[4]{0} convert(%or.2), metadata={op_name="jit(step)/fabric.values/convert_element_type"}
  %select.2 = s32[4]{0} add(%convert.2, %convert.2), metadata={op_name="jit(step)/rem"}
  ROOT %bitcast.2 = s32[4]{0} bitcast(%select.2)
}

%wide.body.sunk (w: (u32[], s32[4])) -> (u32[], s32[4]) {
  %w = (u32[], s32[4]{0}) parameter(0)
  %dynamic-slice.5 = s32[4]{0} get-tuple-element(%w), index=1
  ROOT %tuple.9 = (u32[], s32[4]{0}) tuple(%w, %dynamic-slice.5)
}

ENTRY %main.8 (key.1: u32[4], acc.1: s32[4]) -> s32[4] {
  %key.1 = u32[4]{0} parameter(0), metadata={op_name="key"}
  %acc.1 = s32[4]{0} parameter(1), metadata={op_name="acc"}
  %convert_reduce_fusion.3 = s32[4]{0} fusion(%key.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/fabric.rand/limb_sum/reduce_sum" source_file="x.py" source_line=1}
  %fusion.415 = s32[4]{0} fusion(%key.1), kind=kLoop, calls=%fused_computation.2, backend_config={"flag_configs":[]}
  %while.2 = (u32[], s32[4]{0}) while(%acc.1), condition=%cond, body=%wide.body.sunk
  %all-reduce.3 = s32[4]{0} all-reduce(%fusion.415), metadata={op_name="jit(step)/jit(local_step)/shard_map/fabric.psum/psum"}
  ROOT %add_select_fusion = s32[4]{0} add(%convert_reduce_fusion.3, %all-reduce.3), metadata={op_name="jit(step)/add"}
}
'''


def test_an_op_name_is_cut_to_the_scope_and_what_follows_it():
    cut = scopes.scope_path
    assert cut("jit(step)/fabric.rand/draw/jit(remainder)/rem") == "fabric.rand/draw"
    assert cut("jit(step)/jit(local_step)/shard_map/fabric.psum/psum") == "fabric.psum/psum"
    assert cut("jit(step)/fabric.values/concatenate") == "fabric.values/concatenate"
    assert cut("jit(step)/fabric.rand") == "fabric.rand"
    assert cut("jit(step)/jit(_threefry_fold_in)/add") is None
    assert cut(None) is None and cut("") is None


def test_a_fused_operation_goes_to_its_roots_path():
    paths = scopes.op_paths(HLO)
    # the draw is fused into the reduction: one kernel, reported whole
    assert paths["jit_step/convert_reduce_fusion.3"] == "fabric.rand/limb_sum"
    assert paths["jit_step/all-reduce.3"] == "fabric.psum/psum"


def test_a_fusion_with_a_root_the_compiler_made_goes_where_most_of_it_does():
    paths = scopes.op_paths(HLO)
    # two instructions of the draw, one of the layout, one of the harness,
    # two with no metadata, which do not vote
    assert paths["jit_step/fusion.415"] == "fabric.rand/draw"


def test_what_carries_no_scope_is_unscoped():
    paths = scopes.op_paths(HLO)
    for name in ("while.2", "dynamic-slice.5", "add_select_fusion", "acc.1"):
        assert paths[f"jit_step/{name}"] is None, name
    with pytest.raises(ValueError):
        scopes.op_paths("no module here")


def hand_made():
    """One round of 100 us on two chips. Chip 0 (busy 70 us): the fused draw
    10-40, a compiler's loop 40-60 that spans a 45-55 body operation, the
    harness's add 60-65, an all-reduce 65-70, and an operation of another
    module 80-90. Chip 1 (busy 30 us, the idlest): the fused draw 10-40. Host:
    dispatch 0-10, fold 10-70, epilogue 70-98 with the program's recombine
    72-80 and, inside it, a nested 74-76; reconstruct 90-95; check 98-99."""
    def ev(name, start, end):
        return [name, start * US, (end - start) * US]

    chip0 = {
        "name": "/device:TPU:0",
        "lines": [
            {"name": "XLA Modules", "events": [
                ev("jit_step(123)", 10, 70), ev("jit_fold_in(9)", 80, 90),
            ]},
            {"name": "XLA Ops", "events": [
                ev("convert_reduce_fusion.3", 10, 40), ev("while.2", 40, 60),
                ev("dynamic-slice.5", 45, 55), ev("add_select_fusion", 60, 65),
                ev("all-reduce.3", 65, 70), ev("fusion.415", 80, 90),
            ]},
        ],
    }
    chip1 = {
        "name": "/device:TPU:1",
        "lines": [
            {"name": "XLA Modules", "events": [ev("jit_step(123)", 10, 40)]},
            {"name": "XLA Ops", "events": [ev("convert_reduce_fusion.3", 10, 40)]},
        ],
    }
    host = {
        "name": "/host:CPU",
        "lines": [{"name": "python", "events": [
            ev("round", 0, 100), ev("dispatch", 0, 10), ev("fold", 10, 70),
            ev("epilogue", 70, 98), ev("fabric.epilogue.recombine", 72, 80),
            ev("fabric.epilogue.inner", 74, 76), ev("fabric.reconstruct", 90, 95),
            ev("check", 98, 99),
        ]}],
    }
    return {"planes": [chip0, chip1, host]}


@pytest.fixture(scope="module")
def report():
    return scopes.split(hand_made(), scopes.op_paths(HLO), SPANS)


def test_scoped_and_unscoped_self_seconds_are_the_busy_seconds(report):
    assert report["rounds"] == 1 and report["busiest_chip"] == 0
    chip = report["chips"][0]
    assert chip["busy_s"] == pytest.approx(70e-6)
    assert chip["by_scope"] == pytest.approx({"fabric.rand": 30e-6, "fabric.psum": 5e-6})
    assert chip["by_path"] == pytest.approx(
        {"fabric.rand/limb_sum": 30e-6, "fabric.psum/psum": 5e-6}
    )
    unscoped = chip["unscoped"]
    # the loop keeps what its body does not take; an operation of another
    # module is not joined with the step's text, whatever its name
    assert dict(unscoped["by_name"]) == pytest.approx({
        "jit_step/while.2": 10e-6, "jit_step/dynamic-slice.5": 10e-6,
        "jit_step/add_select_fusion": 5e-6, "jit_fold_in/fusion.415": 10e-6,
    })
    assert unscoped["s"] == pytest.approx(35e-6)
    assert unscoped["share_of_busy"] == pytest.approx(0.5)
    assert sum(chip["by_scope"].values()) + unscoped["s"] == pytest.approx(chip["busy_s"])
    assert report["chips"][1]["unscoped"]["s"] == 0 and report["chips"][1]["busy_s"] == pytest.approx(30e-6)
    assert report["absent"] == []  # every operation of jit_step was found in its text


def test_an_operation_the_steps_text_does_not_hold_is_absent_not_unscoped():
    """A second compile that numbered its fusions differently: the name the
    chip ran is not in the text. Its seconds stay in the busy time, and the
    report says that the join failed for it."""
    paths = scopes.op_paths(HLO.replace("convert_reduce_fusion.3", "convert_reduce_fusion.4"))
    report = scopes.split(hand_made(), paths, SPANS)
    assert report["absent"] == ["jit_step/convert_reduce_fusion.3"]
    chip = report["chips"][0]
    assert dict(chip["unscoped"]["by_name"])["jit_step/convert_reduce_fusion.3"] == pytest.approx(30e-6)
    assert sum(chip["by_scope"].values()) + chip["unscoped"]["s"] == pytest.approx(chip["busy_s"])


def test_host_seconds_by_program_span(report):
    assert report["host_spans_s"] == pytest.approx({
        "fabric.epilogue.recombine": 8e-6, "fabric.epilogue.inner": 2e-6,
        "fabric.reconstruct": 5e-6,
    })


def test_idle_goes_to_the_innermost_program_span_then_the_harnesss_then_none(report):
    idle = report["idle"]
    assert idle["chip"] == 1 and idle["s"] == pytest.approx(70e-6)
    # chip 1 idles 0-10 and 40-100
    assert dict(idle["by_span"]) == pytest.approx({
        "dispatch": 10e-6, "fold": 30e-6, "fabric.epilogue.inner": 2e-6,
        "fabric.epilogue.recombine": 6e-6, "fabric.reconstruct": 5e-6,
        "epilogue": 15e-6, "check": 1e-6, "round": 1e-6,
    })
    assert idle["in_program_spans_share"] == pytest.approx(13 / 70)


def test_a_trace_with_no_device_plane_gives_nothing():
    raw = hand_made()
    raw["planes"] = raw["planes"][2:]
    assert scopes.split(raw, {}, SPANS) is None


@pytest.fixture(scope="module")
def recorded():
    return json.loads((HERE / "recorded-trace-scopes.json").read_text())


def test_the_recorded_trace_is_split_as_on_the_day(recorded):
    """The numbers ``record_scopes_trace.py`` wrote beside the trace on the
    chip: a change to the reader or to ``trace_reduce`` that moves one shows."""
    want = recorded["recorded"]
    got = json.loads(json.dumps(scopes.split(recorded, recorded["paths"], SPANS)))
    assert want["device"] == "TPU v5 lite" and want["rounds"] == got["rounds"] == 3
    assert sorted(got["chips"]) == ["0", "1", "2", "3"]
    for key in ("window_s", "busiest_chip", "host_spans_s"):
        assert got[key] == pytest.approx(want[key]), key
    for chip, numbers in want["chips"].items():
        assert got["chips"][chip]["busy_s"] == pytest.approx(numbers["busy_s"])
        assert got["chips"][chip]["by_path"] == pytest.approx(numbers["by_path"])
        assert got["chips"][chip]["unscoped"]["s"] == pytest.approx(numbers["unscoped"]["s"])
    assert got["idle"]["chip"] == want["idle"]["chip"]
    assert dict(got["idle"]["by_span"]) == pytest.approx(dict(want["idle"]["by_span"]))


def test_the_recorded_trace_holds_the_programs_names(recorded):
    """What the chip's trace showed: every scope of the sharded sum-first
    step on every chip, the collective under its own, and the program's host
    spans as events of the same file, inside the harness's epilogue."""
    report = scopes.split(recorded, recorded["paths"], SPANS)
    assert report["absent"] == []
    for chip in report["chips"].values():
        assert {"fabric.input", "fabric.rand", "fabric.psum"} <= set(chip["by_scope"])
        assert {"fabric.rand/draw", "fabric.rand/limb_sum", "fabric.input/limb_sum"} <= set(
            chip["by_path"]
        )
        scoped = sum(chip["by_scope"].values())
        assert scoped == pytest.approx(sum(chip["by_path"].values()))
        assert scoped + chip["unscoped"]["s"] == pytest.approx(chip["busy_s"], rel=1e-6)
        assert 0 < chip["unscoped"]["share_of_busy"] < 0.5
        assert all(name.startswith("jit_") for name, _ in chip["unscoped"]["by_name"])
    assert set(report["host_spans_s"]) == {
        "fabric.epilogue.recombine", "fabric.epilogue.share_matmul", "fabric.reconstruct",
    }
    assert all(v > 0 for v in report["host_spans_s"].values())
    idle = dict(report["idle"]["by_span"])
    assert all(idle[name] > 0 for name in report["host_spans_s"])
    assert sum(idle.values()) == pytest.approx(report["idle"]["s"])
    # all on one clock: each program span lies inside an epilogue of the harness
    spans = [
        (n, s, s + d) for plane in recorded["planes"] if plane["name"] == "/host:CPU"
        for line in plane["lines"] for n, s, d in line["events"]
    ]
    epilogues = [(s, e) for n, s, e in spans if n == "epilogue"]
    for name, start, end in spans:
        if name.startswith("fabric."):
            assert any(s <= start and end <= e for s, e in epilogues), name


#: the layer files that read the report, and what each reads of it
SCOPE_LAYERS = {
    "engine.input_s": "fabric.input", "engine.rand_s": "fabric.rand",
    "engine.layout_s": "fabric.values", "engine.share_matmul_s": "fabric.share_matmul",
    "engine.unscoped_s": "unscoped",
}
HOST_LAYERS = {
    "epilogue.recombine_s": "fabric.epilogue.recombine",
    "epilogue.share_matmul_s": "fabric.epilogue.share_matmul",
    "epilogue.reconstruct_s": "fabric.reconstruct",
}


def context(report):
    """What the harness tells a layer file of a traced run with this report
    (``harness.run_cell``: no scopes where an operation was absent)."""
    cell = harness.load_cell(bench_tree.REPO, "c5-sumfirst-x4")
    return harness.LayerContext(
        name=cell.name, chips=4, config=cell.config, traffic=cell.traffic, rounds=report["rounds"],
        elements_per_round=1, chunk_bytes=1, acc_bytes=1, steps_per_round=1, plan=None, peaks=None,
        memory_peak_bytes=0, log=lambda message: None,
        scopes=None if report["absent"] else report, host_spans=report["host_spans_s"],
    )


@pytest.mark.parametrize("metric", sorted(SCOPE_LAYERS) + sorted(HOST_LAYERS))
def test_a_layer_file_returns_the_recorded_traces_seconds(recorded, metric):
    """Each metric that reads the program's names is the report's own number:
    the same reduction as ``scopes.py`` prints, so equal to it by construction."""
    report = scopes.split(recorded, recorded["paths"], SPANS)
    module = harness.load_layers(bench_tree.REPO)[metric]
    value = module.reduce([], None, context(report))
    busiest = report["chips"][report["busiest_chip"]]
    if metric in HOST_LAYERS:
        assert value == report["host_spans_s"][HOST_LAYERS[metric]] > 0
    elif SCOPE_LAYERS[metric] == "unscoped":
        assert value == busiest["unscoped"]["s"] > 0
    elif SCOPE_LAYERS[metric] in busiest["by_scope"]:
        assert value == busiest["by_scope"][SCOPE_LAYERS[metric]] > 0
        want = recorded["recorded"]["chips"][str(report["busiest_chip"])]["by_path"]
        under = sum(v for k, v in want.items() if k.split("/")[0] == SCOPE_LAYERS[metric])
        assert value == pytest.approx(under)  # the seconds written on the chip, on the day
    else:
        # the sharded sum-first step has no share matmul and no layout of its
        # own: a reader that finds nothing returns nothing, never 0
        assert SCOPE_LAYERS[metric] in ("fabric.values", "fabric.share_matmul") and value is None


def test_the_scope_metrics_and_unscoped_are_the_busy_seconds(recorded):
    report = scopes.split(recorded, recorded["paths"], SPANS)
    busiest = report["chips"][report["busiest_chip"]]
    total = sum(busiest["by_scope"].values()) + scopes.scope_seconds(report, scopes.UNSCOPED)
    assert total == pytest.approx(busiest["busy_s"], rel=1e-6)
    assert scopes.scope_seconds(None, "fabric.rand") is None
    assert scopes.scope_seconds(report, "fabric.nothing") is None


def test_with_an_operation_absent_no_scope_metric_is_reported(recorded):
    """A second compile numbered differently: seconds would go to the wrong
    scope, so none is reported. The host's spans do not pass through the
    join, and stay."""
    paths = dict(recorded["paths"])
    del paths[min(name for name, path in paths.items() if path == "fabric.psum/psum")]
    report = scopes.split(recorded, paths, SPANS)
    assert report["absent"]
    layers = harness.load_layers(bench_tree.REPO)
    told = context(report)
    assert all(layers[name].reduce([], None, told) is None for name in SCOPE_LAYERS)
    assert all(layers[name].reduce([], None, told) > 0 for name in HOST_LAYERS)


def fake_run(monkeypatch, raw, paths):
    """``main`` with the chips and the traced run replaced by a recorded
    trace: what it prints and returns, past the run."""
    from benchmark import run

    line = {
        "correct": True, "device": {"kind": "TPU v5 lite"},
        "metrics": {"kernel.busy_s": {"value": 1.0, "unit": "s"}},
    }
    report = json.loads(json.dumps(scopes.split(raw, paths, SPANS)))  # as the record holds it
    monkeypatch.setattr(run, "acquire_chips", lambda chips: ["chip"] * chips)
    monkeypatch.setattr(scopes, "trace_cell", lambda *args: (line, report))
    return scopes.main(["--workload", "c4-sumfirst", "--seed", "3"])


def test_main_prints_one_line_and_passes_a_scoped_trace(recorded, monkeypatch, capsys):
    assert fake_run(monkeypatch, recorded, recorded["paths"]) == 0
    out = capsys.readouterr().out.splitlines()
    assert len(out) == 1
    line = json.loads(out[0])
    assert line["workload"] == "c4-sumfirst" and line["correct"] is True
    assert line["metrics"] == {"kernel.busy_s": 1.0}
    assert {"rounds", "window_s", "busiest_chip", "chips", "host_spans_s", "idle"} <= set(line)
    assert line["absent"] == []


def test_main_fails_a_trace_with_an_operation_its_text_does_not_hold(recorded, monkeypatch, capsys):
    """One name missing is enough, however small its share: the split cannot
    be trusted where the text is of another numbering."""
    paths = dict(recorded["paths"])
    missing = min(name for name, path in paths.items() if path == "fabric.psum/psum")
    del paths[missing]
    assert fake_run(monkeypatch, recorded, paths) == scopes.EXIT_UNSCOPED
    captured = capsys.readouterr()
    assert json.loads(captured.out)["absent"] == [missing]
    assert "not in its compiled text" in captured.err and missing in captured.err


def test_main_fails_a_trace_whose_operations_are_all_unscoped(recorded, monkeypatch, capsys):
    """What a compile cache filled by the parent commit gives (the cache's
    key strips debug info), or a join that found no name."""
    assert fake_run(monkeypatch, recorded, {}) == scopes.EXIT_UNSCOPED
    captured = capsys.readouterr()
    line = json.loads(captured.out)
    assert all(c["unscoped"]["share_of_busy"] == pytest.approx(1.0) for c in line["chips"].values())
    assert "unscoped" in captured.err


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return bench_tree.tiny_tree(tmp_path_factory.mktemp("scopes_copy"))


@pytest.mark.parametrize("workload,scoped", [
    ("tiny-c5-sumfirst-x4", {
        "fabric.rand/draw", "fabric.rand/limb_sum", "fabric.input/limb_sum", "fabric.psum/psum",
    }),
    ("tiny-c4-participant", {
        "fabric.rand/draw", "fabric.values/concatenate", "fabric.share_matmul/limbs",
        "fabric.share_matmul/dot", "fabric.combine/reduce_sum",
    }),
])
def test_every_operation_of_a_cpu_trace_is_found_in_the_compiled_text(tree, tmp_path, workload, scoped):
    """The join on instruction names, held without a chip: a CPU trace names
    an operation by ``hlo_op`` under ``hlo_module``, and the text compiled
    for the same devices holds every one of them."""
    import jax
    from jax.profiler import ProfileData

    devices = jax.devices("cpu")
    cell = harness.load_cell(tree, workload)
    paths = scopes.join_table(harness.round_of(cell).steps(cell, devices))
    assert scoped <= set(paths.values())
    harness.run_cell(
        tree, workload, 2, 0.05, True, devices, time.perf_counter(),
        out_dir=tmp_path, log=lambda message: None, keep_trace=True,
    )
    trace = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    ran = set()
    for plane in ProfileData.from_file(str(trace)).planes:
        for line in plane.lines:
            for event in line.events:
                stats = dict(event.stats)
                if stats.get("hlo_module") == "jit_step":
                    ran.add(f"jit_step/{stats['hlo_op']}")
    assert len(ran) >= 5
    assert ran <= set(paths), sorted(ran - set(paths))
    assert {paths[name] for name in ran} & scoped
    raw = scopes.load(trace, SPANS)
    # host events are kept by prefix: the program's spans beside the harness's
    kept = {n for plane in raw["planes"] for line in plane["lines"] for n, _s, _d in line["events"]}
    assert {n for n in kept if n.startswith("fabric.")} == {
        n for n in ("fabric.epilogue.recombine", "fabric.epilogue.share_matmul", "fabric.reconstruct")
        if n != "fabric.epilogue.share_matmul" or "sumfirst" in workload
    }
    assert set(SPANS) <= kept
    assert scopes.split(raw, paths, SPANS) is None  # no device plane: no device number invented
