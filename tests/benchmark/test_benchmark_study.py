"""The spread study's arithmetic: a shorter window cut from a run's kept
rounds, and the table of scatter inside runs against shift between them."""

import json

import pytest

from benchmark import study


def record(label, seed, round_s, setup_s=10.0, elements=1000.0):
    starts = [sum(round_s[:i]) for i in range(len(round_s))]
    window = sum(round_s)
    return {
        "set": label, "seed": seed, "seconds": 6.0, "device": {"kind": "TPU v5 lite"},
        "round_s_each": round_s, "round_start_s_each": starts, "window_s": window,
        "round_s": sorted(round_s)[len(round_s) // 2], "setup_s": setup_s,
        "elems_per_s": len(round_s) * elements / window, "round_spread": 0.0,
        "warmup_round_s": [1.0],
        "spans": {"fold": [0.25 * s for s in round_s], "epilogue": [0.75 * s for s in round_s]},
        "line": {"correct": True, "failed": 0, "device": {"memory_peak_bytes": 5}},
    }


def test_cut_keeps_the_rounds_that_had_started_by_the_shorter_window():
    run = record("A", 1, [1.0, 2.0, 3.0, 1.0])  # starts 0, 1, 3, 6
    whole = study.cut(run, 7.0)
    assert whole["rounds"] == 4 and whole["elems_per_s"] == pytest.approx(run["elems_per_s"])
    short = study.cut(run, 3.0)  # the round that starts at 3.0 had not started
    assert short["rounds"] == 2
    assert short["round_s"] == pytest.approx(1.5)
    assert short["elems_per_s"] == pytest.approx(2 * 1000.0 / 3.0)
    assert study.cut(run, 3.5)["rounds"] == 3


def test_collect_writes_every_round_and_the_spread_of_each_set(tmp_path, monkeypatch):
    cell = tmp_path / "study" / "some-cell"
    cell.mkdir(parents=True)
    runs = {
        "A": [[1.0] * 6, [1.1] * 6, [1.2] * 6],
        "B": [[1.0] * 6, [1.0] * 6, [1.0] * 6],
    }
    seed = 0
    for label, sets in runs.items():
        for rounds in sets:
            seed += 1
            (cell / f"{label}-seed{seed}.json").write_text(json.dumps(record(label, seed, rounds)))
    monkeypatch.setattr(study, "ROOT", tmp_path)
    (tmp_path / "benchmark" / "out").mkdir(parents=True)
    assert study.collect(tmp_path / "study", windows=[3.0]) == 0
    out = json.loads((tmp_path / "benchmark/out/spread-some-cell.json").read_text())
    assert len(out["runs"]) == 6 and out["runs"][0]["round_s_each"] == [1.0] * 6
    assert set(out["windows"]) == {"3", "6"}
    whole = out["windows"]["6"]
    # set A's quartiles of (1.0, 1.1, 1.2), as statistics.quantiles(n=4) and
    # the driver take them, are 1.0 and 1.2: 0.2 / 1.1
    assert whole["sets"]["A"]["round_s"]["spread"] == pytest.approx(0.2 / 1.1)
    assert whole["sets"]["A"]["round_s"]["range"] == pytest.approx(0.2 / 1.1)
    assert whole["sets"]["B"]["round_s"]["spread"] == 0.0
    assert whole["widest_set_spread"]["round_s"] == pytest.approx(0.2 / 1.1)
    assert whole["widest_set_range"]["round_s"] == pytest.approx(0.2 / 1.1)
    # where the shift lives: three quarters of it in the epilogue
    assert out["runs"][1]["span_median_s"] == pytest.approx({"fold": 0.275, "epilogue": 0.825})
    assert out["span_shift"]["epilogue"] == pytest.approx(
        {"median_s": 0.75, "range_s": 0.15, "widest_set_range_s": 0.15}
    )
    assert out["span_shift"]["fold"]["range_s"] == pytest.approx(0.05)
    assert whole["set_medians_apart"]["round_s"] == pytest.approx(0.1 / 1.05)
    assert whole["inside_run_scatter"] == 0.0
