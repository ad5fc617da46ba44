"""The reduction from trace to numbers: on a hand-made trace whose numbers
can be worked out on paper, and on a small trace recorded on the chip
(``recorded-trace-x4.json``, made by ``record_trace.py``)."""

import dataclasses
import json
import pathlib

import pytest

from benchmark import trace_reduce
from benchmark.rounds import packed_fold

SPAN_NAMES = ("round", *packed_fold.span_names)

HERE = pathlib.Path(__file__).resolve().parent
US = 1000.0  # nanoseconds


def hand_made():
    """Two chips, one round of 100 us. Chip 0: a module of three operations
    (10-30, 30-50, 70-90 us), the second a while that spans a 35-45 us body
    operation; an all-reduce from 50 to 60 us, 5 us of it under a copy. Chip 1:
    one 20-40 us operation. Host: dispatch 0-20, fold 20-60, fetch 60-65,
    epilogue 65-95, check 95-99."""
    def ev(name, start, end):
        return [name, start * US, (end - start) * US]

    chip0 = {
        "name": "/device:TPU:0",
        "lines": [
            {"name": "XLA Modules", "events": [ev("jit_step(123)", 10, 90)]},
            {"name": "XLA Ops", "events": [
                ev("fusion.1", 10, 30), ev("while.2", 30, 50), ev("body.3", 35, 45),
                ev("all-reduce-start", 50, 51), ev("copy.4", 55, 60),
                ev("all-reduce-done", 59, 60), ev("fusion.5", 70, 90),
            ]},
            {"name": "Async XLA Ops", "events": [ev("all-reduce-start", 50, 60)]},
        ],
    }
    chip1 = {
        "name": "/device:TPU:1",
        "lines": [{"name": "XLA Ops", "events": [ev("fusion.1", 20, 40)]}],
    }
    host = {
        "name": "/host:CPU",
        "lines": [{"name": "python", "events": [
            ev("round", 0, 100), ev("dispatch", 0, 20), ev("fold", 20, 60),
            ev("fetch", 60, 65), ev("epilogue", 65, 95), ev("check", 95, 99),
            ev("something else", 0, 100),
        ]}],
    }
    return {"planes": [chip0, chip1, host, {"name": "/host:metadata", "lines": []}]}


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(hand_made(), SPAN_NAMES)


def test_window_is_the_rounds(reduced):
    assert reduced.window == (0.0, 100 * US)
    assert reduced.window_seconds == pytest.approx(100e-6)
    assert reduced.chips == [0, 1]


def test_busy_is_the_union_of_operation_intervals(reduced):
    # chip 0: 10-50 (nested body counted once), 50-51, 55-60, 70-90
    assert reduced.busy_seconds(0) == pytest.approx(66e-6)
    assert reduced.busy_seconds(1) == pytest.approx(20e-6)
    assert reduced.mean_busy_seconds() == pytest.approx(43e-6)
    assert reduced.max_busy_seconds() == pytest.approx(66e-6)


def test_busy_of_named_programs_leaves_the_others_operations_out(reduced):
    # chip 0's operations all run inside jit_step; chip 1's trace names no
    # module, so its operation is no program's
    assert reduced.busy_seconds(0, {"jit_step"}) == pytest.approx(66e-6)
    assert reduced.busy_seconds(1, {"jit_step"}) == 0.0
    assert reduced.max_busy_seconds({"jit_step"}) == pytest.approx(66e-6)
    assert reduced.max_busy_seconds({"jit_other"}) == 0.0
    assert reduced.max_busy_seconds(frozenset()) == 0.0


def test_idle_share_is_the_idlest_chips(reduced):
    assert reduced.idlest_chip() == 1
    assert reduced.idle_share() == pytest.approx(0.8)


def test_operations_are_named_by_module_and_ranked_by_self_time(reduced):
    top = dict(reduced.top_operations(10))
    # averaged over the two chips; the while keeps what its body does not take
    assert top["jit_step/fusion.1"] == pytest.approx(10e-6)
    assert top["fusion.1"] == pytest.approx(10e-6)  # chip 1 has no module line
    assert top["jit_step/fusion.5"] == pytest.approx(10e-6)
    assert top["jit_step/while.2"] == pytest.approx(5e-6)
    assert top["jit_step/body.3"] == pytest.approx(5e-6)
    assert [name for name, _ in reduced.top_operations(2)][0] in (
        "jit_step/fusion.1", "fusion.1", "jit_step/fusion.5",
    )
    assert len(reduced.top_operations(3)) == 3


def test_collective_time_runs_from_start_to_done_and_exposed_is_what_nothing_covers(reduced):
    total, exposed = reduced.collective_seconds()
    assert total == pytest.approx(10e-6)
    assert exposed == pytest.approx(5e-6)  # 55-60 is under copy.4


def test_idle_gaps_are_named_by_the_span_the_host_was_in(reduced):
    gaps = dict(reduced.idle_gaps_by_span(10))
    # the idlest chip (1) is idle 0-20 and 40-100
    assert gaps["dispatch"] == pytest.approx(20e-6)
    assert gaps["fold"] == pytest.approx(20e-6)
    assert gaps["fetch"] == pytest.approx(5e-6)
    assert gaps["epilogue"] == pytest.approx(30e-6)
    assert gaps["check"] == pytest.approx(4e-6)
    assert gaps["round"] == pytest.approx(1e-6)
    assert gaps["-"] == pytest.approx(0.0)
    assert sum(gaps.values()) == pytest.approx(80e-6)


def test_operations_are_clipped_to_the_window():
    raw = hand_made()
    raw["planes"][1]["lines"][0]["events"].append(["late.9", 95 * US, 20 * US])
    reduced = trace_reduce.reduce(raw, SPAN_NAMES)
    assert reduced.busy_seconds(1) == pytest.approx(25e-6)


def test_a_trace_with_no_device_plane_reduces_to_nothing():
    raw = {"planes": [p for p in hand_made()["planes"] if "TPU" not in p["name"]]}
    assert trace_reduce.reduce(raw, SPAN_NAMES) is None


@pytest.mark.parametrize("intervals,cover,bare", [
    ([[0, 10]], [], [[0, 10]]),
    ([[0, 10]], [[2, 4], [6, 12]], [[0, 2], [4, 6]]),
    ([[0, 10], [20, 30]], [[5, 25]], [[0, 5], [25, 30]]),
    ([[0, 10]], [[0, 10]], []),
])
def test_subtract(intervals, cover, bare):
    assert trace_reduce.subtract(intervals, cover) == bare


def test_merge_and_short_name():
    assert trace_reduce.merge([(5, 7), (0, 3), (2, 4), (7, 9), (1, 1)]) == [[0, 4], [5, 9]]
    assert trace_reduce.short_name("%fusion.67 = (u32[2]{0}) fusion(u32[] %x)") == "fusion.67"
    assert trace_reduce.short_name("round") == "round"


RECORDED = HERE / "recorded-trace-x4.json"


@pytest.fixture(scope="module")
def recorded():
    raw = json.loads(RECORDED.read_text())
    return raw["recorded"], trace_reduce.reduce(raw, SPAN_NAMES)


def test_recorded_trace_has_the_planes_and_lines_the_reduction_reads():
    raw = json.loads(RECORDED.read_text())
    names = {p["name"] for p in raw["planes"]}
    assert {f"/device:TPU:{i}" for i in range(4)} <= names
    for plane in raw["planes"]:
        if trace_reduce.DEVICE_PLANE.fullmatch(plane["name"]):
            assert trace_reduce.OPS_LINE in {line["name"] for line in plane["lines"]}
    assert raw["recorded"]["device"] == "TPU v5 lite"


def test_recorded_trace_reduces_to_the_numbers_of_the_day(recorded):
    want, reduced = recorded
    assert reduced.chips == [0, 1, 2, 3]
    assert reduced.window_seconds == pytest.approx(want["window_s"])
    for chip, busy in want["busy_s_by_chip"].items():
        assert reduced.busy_seconds(int(chip)) == pytest.approx(busy)
        assert 0 < busy < want["window_s"]
    assert reduced.idle_share() == pytest.approx(want["idle_share"])
    total, exposed = reduced.collective_seconds()
    assert total == pytest.approx(want["collective_s"]) and total > 0
    assert exposed == pytest.approx(want["collective_exposed_s"]) and exposed <= total
    top = reduced.top_operations(5)
    assert [n for n, _ in top] == [n for n, _ in want["top_operations"]]
    assert all(name.startswith("jit_") for name, _ in top)
    gaps = dict(reduced.idle_gaps_by_span(10))
    assert gaps == pytest.approx(dict(want["idle_gaps"]))
    assert sum(gaps.values()) == pytest.approx(want["idle_share"] * want["window_s"])


def test_every_layer_metric_reduces_the_recorded_trace_to_a_number(recorded):
    """The layer files' own arithmetic, which a CPU run never reaches (its
    trace holds no device plane)."""
    import types

    from benchmark import harness, models, traffic

    repo = HERE.parents[1]
    _want, reduced = recorded
    cell = harness.load_cell(repo, "c5-sumfirst-x4")
    spans = [
        harness.Span(name, 1, start * 1e-9, end * 1e-9)
        for name, start, end in reduced.host_spans
    ]
    rounds = sum(s.name == "round" for s in spans)
    plan = types.SimpleNamespace(
        modulus=(1 << 60) + 33, input_size=5, rand_size=2, share_count=8, n_batches=13
    )
    lines = []
    context = harness.LayerContext(
        name=cell.name, chips=4, config=cell.config, traffic=cell.traffic, rounds=rounds,
        elements_per_round=8 * 62, chunk_bytes=8 * 62 * 8, acc_bytes=2 * 13 * 7 * 8, steps_per_round=4, plan=plan,
        peaks=harness.load_peaks(repo, "TPU v5 lite"), memory_peak_bytes=5 << 30,
        log=lines.append, chunk_step_modules=frozenset({"jit_step"}),
    )
    values = {
        name: module.reduce(spans, reduced, context)
        for name, module in harness.load_layers(repo).items()
    }
    # this trace holds none of the program's names and the context no report
    # of them: the metrics that read them find nothing, and say nothing
    silent = {name for name, value in values.items() if value is None}
    assert silent == {
        "engine.input_s", "engine.rand_s", "engine.layout_s", "engine.share_matmul_s",
        "engine.unscoped_s", "epilogue.recombine_s", "epilogue.share_matmul_s",
        "epilogue.reconstruct_s",
    }
    values = {name: value for name, value in values.items() if name not in silent}
    assert all(isinstance(v, float) and v > 0 for v in values.values()), values
    busy = reduced.max_busy_seconds()
    assert values["kernel.busy_s"] == pytest.approx(busy / rounds)
    assert values["device.idle_share"] == pytest.approx(100 * reduced.idle_share())
    assert values["device.peak_gib"] == 5.0
    assert values["collective.exposed_share"] == pytest.approx(100.0)
    least, binds = models.least_seconds(8 * 62 * 8 // 4 + 2 * 2 * 13 * 7 * 8, 0, context.peaks)
    assert binds == "hbm" and "hbm binds" in lines[0]
    # the chunk step's share is of its own program's device time: the rounds'
    # jit_fold_in, which the trace holds too, is not the step's
    step_busy = reduced.max_busy_seconds({"jit_step"})
    for chip in reduced.chips:
        others = reduced.busy_seconds(chip, {"jit_fold_in"})
        assert 0 < others < 0.1 * reduced.busy_seconds(chip)
        assert reduced.busy_seconds(chip, {"jit_step"}) + others == pytest.approx(
            reduced.busy_seconds(chip), rel=1e-9
        )
    assert step_busy < busy
    assert values["chunk_step_roofline"] == pytest.approx(100 * least / (step_busy / (rounds * 4)))
    silent_context = dataclasses.replace(context, chunk_step_modules=frozenset({"jit_not_there"}))
    layers = harness.load_layers(repo)
    assert layers["chunk_step_roofline"].reduce(spans, reduced, silent_context) is None
    assert layers["kernel.busy_s"].reduce(spans, reduced, silent_context) == pytest.approx(busy / rounds)
    assert values["engine.fold_s"] > 0 and values["epilogue.s"] > 0
    whole = [s for s in spans if s.name == "round"]
    elapsed = max(s.end for s in whole) - min(s.start for s in whole)
    assert values["elems_per_s"] == pytest.approx(rounds * 8 * 62 / elapsed)
    assert traffic.load(repo / "benchmark/traffic/participant-narrow.json").share_matmul_in_step
