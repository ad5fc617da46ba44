"""The seven layer metrics that read the program's timeline: the four of
set-up under ``benchmark/layers/`` (JAX's trace / lower / compile / load events,
kept by the program in its span ring by program name, taken before the window)
and the three of the host feed, dropped into a copy as the files a ``benchmark``
PR would add (its wait on each bound and its own seconds, cut by the harness's
``round`` spans: one clock, no profiler). The feed's wait in ``timeline_layers/``
for the ``benchmark`` PR that evaluates a metric that reads no trace in an
untraced run (a traced run's link is the profiler's; PERF.md section 7). A
traced CPU run through the harness reports all of them that apply: the ring
needs no device plane."""

import json
import os
import pathlib
import shutil
import statistics
import subprocess
import sys
import types

import pytest

import bench_tree
import cell_checks
from benchmark import harness
from test_benchmark_hostfed import TINY as HOSTFED
from test_benchmark_hostfed import add_tiny_hostfed

HERE = pathlib.Path(__file__).resolve().parent
LAYER_FILES = HERE / "timeline_layers"
SUMFIRST = bench_tree.TINY_CELLS[0]
SETUP_METRICS = ["setup.trace_s", "setup.lower_s", "setup.load_s", "setup.compile_s"]
FEED_METRICS = ["feed.link_wait_s", "feed.in_flight_wait_s", "feed.own_s"]
ALREADY = {"engine.fold_s", "epilogue.s", "elems_per_s"}  # what a traced CPU run reports


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """The feed's three files and their entries for the host-fed twin; the
    twin joins the set-up metrics' lists as every twin of a listed cell does,
    and the sum-first twin is added to them by hand."""
    root = bench_tree.copy_benchmark(tmp_path_factory.mktemp("timeline") / "copy")
    for file in sorted(LAYER_FILES.glob("*.py")):
        shutil.copy(file, root / "benchmark/layers" / file.name)
    add_tiny_hostfed(root)
    bench_tree.add_cell(root, *SUMFIRST)
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    layers = harness.load_layers(root)
    for metric in manifest["per_layer"]:
        if metric["name"] in SETUP_METRICS:
            metric["workloads"].append(SUMFIRST[0])
    for name in FEED_METRICS:
        manifest["per_layer"].append({
            "name": name, "unit": "s", "better": "lower", "source": "program_span",
            "layer": layers[name].layer, "moves": layers[name].moves, "workloads": [HOSTFED],
        })
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root


@pytest.fixture(scope="module")
def runs(tree):
    """Both cells traced in one new process, the host-fed one first."""
    done = subprocess.run(
        [sys.executable, str(HERE / "run_timeline_cells.py"), str(tree), HOSTFED, SUMFIRST[0]],
        capture_output=True, text=True, timeout=600, cwd=bench_tree.REPO,
        env=dict(os.environ, PYTHONPATH=os.pathsep.join([str(bench_tree.REPO), str(HERE)])),
    )
    assert done.returncode == 0, done.stderr[-3000:]
    return {run["cell"]: run for run in map(json.loads, done.stdout.strip().splitlines())}


def test_the_timeline_layer_files_are_layer_files_and_pass_the_cell_checks(tree):
    layers = harness.load_layers(tree)
    assert sorted(p.stem for p in LAYER_FILES.glob("*.py")) == [
        "feed_in_flight_wait_s", "feed_link_wait_s", "feed_own_s",
    ]
    listed = {m["name"]: m for m in cell_checks.manifest_of(bench_tree.REPO)["per_layer"]}
    every_cell = [w["name"] for w in cell_checks.manifest_of(bench_tree.REPO)["workloads"]]
    for name in SETUP_METRICS:
        module = layers[name]
        assert (module.unit, module.layer, module.moves) == ("s", "set-up", "setup_s")
        assert module.reads_spans == () and module.reads == ("ring:jax.",)
        assert listed[name]["workloads"] == every_cell
        source = (bench_tree.REPO / "benchmark/layers" / f"{name.replace('.', '_')}.py").read_text()
        assert "import sda_tpu" not in source and "from sda_tpu" not in source
    for name in FEED_METRICS:
        module = layers[name]
        assert (module.unit, module.layer, module.moves) == ("s", "host feed", "round_s")
        assert module.reads_spans == ("round",)
        source = (LAYER_FILES / f"{name.replace('.', '_')}.py").read_text()
        assert "import sda_tpu" not in source and "from sda_tpu" not in source
    cell_checks.check_cell(tree, HOSTFED)
    cell_checks.check_cell(tree, SUMFIRST[0])


def test_a_traced_cpu_run_reports_every_timeline_metric_that_applies(runs):
    fed, resident = runs[HOSTFED]["line"], runs[SUMFIRST[0]]["line"]
    assert fed["correct"] is True and resident["correct"] is True
    assert set(fed["metrics"]) == ALREADY | set(SETUP_METRICS) | set(FEED_METRICS)
    assert set(resident["metrics"]) == ALREADY | set(SETUP_METRICS)
    assert all(m["unit"] == "s" for name, m in fed["metrics"].items() if name not in ALREADY)
    assert runs[HOSTFED]["dropped"] == [] and runs[SUMFIRST[0]]["dropped"] == []


@pytest.mark.parametrize("cell", [HOSTFED, SUMFIRST[0]])
def test_the_set_up_metrics_are_the_seconds_of_the_records_before_the_window(runs, cell):
    run = runs[cell]
    value = {name: run["line"]["metrics"][name]["value"] for name in SETUP_METRICS}
    records = run["jax_before_window"]
    by_name = lambda name: [r for r in records if r["name"] == name]
    # the tests run with no compile cache: every program is compiled, none loaded
    assert value["setup.load_s"] == 0 and not by_name("jax.cache_load")
    for name, record in (("setup.lower_s", "jax.lower"), ("setup.compile_s", "jax.compile")):
        assert value[name] > 0
        assert value[name] == pytest.approx(sum(r["duration_s"] for r in by_name(record)))
    traced = by_name("jax.trace")
    assert 0 < value["setup.trace_s"] <= sum(r["duration_s"] for r in traced)
    assert value["setup.trace_s"] >= max(r["duration_s"] for r in traced)
    # the round's programs are there by name, and the window compiled nothing
    compiled = {r["attrs"]["program"] for r in by_name("jax.compile")}
    assert {"jit_step", "jit_make"} <= compiled  # the chunk step, the input's maker
    # what the process had compiled before (the first cell's) is in nobody's set-up twice
    assert ("jit_fold_in" in compiled) == (cell == HOSTFED)
    assert run["line"]["compared"]["compiles_in_window"]["value"] == 0
    # one after another on one thread: together they fit the run's set-up
    assert sum(value.values()) < run["setup_s"]


def test_the_feed_metrics_are_the_median_round_and_a_round_adds_up(runs):
    run = runs[HOSTFED]
    value = {name: run["line"]["metrics"][name]["value"] for name in FEED_METRICS}
    per_round = []
    for since, until in run["rounds"]:
        mine = [r for r in run["feed"] if since <= r["start_mono"] < until]
        took = lambda name, **attrs: sum(
            r["duration_s"] for r in mine
            if r["name"] == name and all(r["attrs"][k] == v for k, v in attrs.items())
        )
        (call,) = [r for r in mine if r["name"] == "fabric.feed"]
        row = {
            "call": call["duration_s"], "put": took("fabric.feed.put"),
            "feed.link_wait_s": took("fabric.feed.wait", on="link"),
            "feed.in_flight_wait_s": took("fabric.feed.wait", on="in_flight"),
        }
        row["feed.own_s"] = row["call"] - row["put"] - took("fabric.feed.wait")
        # a round adds up: own seconds, puts and waits are the call
        assert row["feed.own_s"] > 0
        assert sum(r["attrs"]["bytes"] for r in mine if r["name"] == "fabric.feed.put") == (
            call["attrs"]["bytes"]
        )
        per_round.append(row)
    assert len(per_round) == run["line"]["attempted"] >= 2
    for name in FEED_METRICS:
        assert value[name] == pytest.approx(statistics.median(r[name] for r in per_round)), name
    # one block waits on the caller's bound a round (four blocks, three alive)
    assert value["feed.in_flight_wait_s"] > 0
    assert value["feed.own_s"] < statistics.median(r["call"] for r in per_round)


def test_each_timeline_metric_says_nothing_of_an_empty_ring(tree):
    """A program without the records (the parent's), or a ring nobody wrote:
    no number, and no error."""
    from sda_tpu import telemetry

    layers = harness.load_layers(tree)
    spans = [harness.Span("round", 1, 10.0, 11.0), harness.Span("round", 2, 11.0, 12.0)]
    telemetry.reset()

    def context():
        taken = {name: layers[name].before_window(0.0) for name in SETUP_METRICS}
        return types.SimpleNamespace(host_spans=None, before_window=taken)

    for name in SETUP_METRICS + FEED_METRICS:
        assert layers[name].reduce(spans, None, context()) is None, name
        assert layers[name].reduce([], None, context()) is None, name
    # records from before the field existed, and records that are not set-up's
    try:
        with telemetry.span("fabric.feed.put", rows=1, bytes=8) as put:
            pass
        with telemetry.span("fabric.feed.wait", on="link") as wait:
            pass
        del put["start_mono"], wait["start_mono"]
        spans = [harness.Span("round", 1, 0.0, 1e12)]
        for name in SETUP_METRICS + FEED_METRICS:
            assert layers[name].reduce(spans, None, context()) is None, name
    finally:
        telemetry.reset()
