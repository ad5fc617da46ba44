"""The benchmark's harness: finds a cell's files by the names in
``BENCHMARK.json``, has the cell's round set itself up on the devices it is
handed, runs rounds back to back for the window, and reduces what it saw to
the result line.

It knows no cell, configuration, traffic mix, metric, scheme or round by
name, and imports nothing of the program:

* a cell is one entry of ``BENCHMARK.json``'s ``workloads``;
* its configuration is ``benchmark/configs/<config>.json`` (field, scheme,
  dim, dropped clerks, guarantees);
* its traffic mix is ``benchmark/traffic/<traffic>.json`` (rows, passes,
  chunk, mesh), read by the one generator in :mod:`benchmark.traffic`;
* its round is the module the traffic file names (``round``; by default
  ``benchmark.rounds.packed_fold``), of the interface :mod:`benchmark.rounds`
  describes: it builds the scheme and the step, makes the input and the
  reference, and runs one round from key to comparison under its own spans;
* a per-layer metric is one module in ``benchmark/layers/``, found by listing
  the directory; it says what it reads (``reads_spans``, ``reads``), so that
  where a run lacks that the metric is known to be silent without a chip.

What is the harness's own, common to every round: the window with its
failure rules, the spans' clock, the count of compiles inside the window,
tracing and its reduction, the layer metrics, the result line and the run's
record.

The devices are handed in by the caller: ``run.py`` hands in TPU chips or
exits, the tests hand in CPU devices. Nothing here picks a platform.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import importlib.util
import json
import pathlib
import shutil
import statistics
import time
import traceback

import numpy as np

from benchmark import scopes, trace_reduce
from benchmark import traffic as traffic_mod

#: the harness's own span, round every round; a round module names the rest
ROUND_SPAN = "round"

#: a traced window closes after this many seconds (or ``--seconds``, if
#: shorter): traces are large and tracing slows the host, so the per-layer
#: run is short and the end-to-end numbers come from the untraced run
TRACE_WINDOW_SECONDS = 12.0

#: a window that fails this many rounds in a row stops
MAX_CONSECUTIVE_FAILURES = 3

#: what a layer file may say it reads beyond the round's spans (``reads``: a
#: tuple of ``"<kind>:<name>"``), by kind: ``scope`` the seconds under one of the
#: program's device scopes or paths, or ``unscoped`` (``LayerContext.scopes``);
#: ``host_span`` one of the program's own host spans (``LayerContext.host_spans``);
#: ``kernel`` the device operations of that name, numbered (``chacha_rounds.1``),
#: in the trace; ``ring`` the program's span records of that prefix, which the
#: file takes itself before the window (``before_window``)
READS = ("scope", "host_span", "kernel", "ring")


class HarnessError(RuntimeError):
    """The manifest or one of a cell's files is missing or malformed."""


# ---------------------------------------------------------------------------
# Finding a cell's files
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: traffic_mod.Traffic
    end_to_end: tuple  # manifest entries this cell reports
    per_layer: tuple  # manifest entries this cell reports
    root: pathlib.Path

    @property
    def participants(self) -> int:
        return self.traffic.rows * self.traffic.passes

    @property
    def dim(self) -> int:
        return int(self.config["dim"])

    @property
    def elements_per_round(self) -> int:
        return self.participants * self.dim


def load_manifest(root) -> dict:
    path = pathlib.Path(root) / "BENCHMARK.json"
    try:
        return json.loads(path.read_text())
    except OSError as e:
        raise HarnessError(f"no manifest at {path}: {e}") from e


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(root, workload: str) -> Cell:
    root = pathlib.Path(root)
    manifest = load_manifest(root)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise HarnessError(f"no workload {workload!r}; there are {sorted(cells)}")
    entry = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    if entry["config"] not in configs:
        raise HarnessError(f"workload {workload!r} names no listed config")
    config_path = root / configs[entry["config"]]["file"]
    traffic_path = root / "benchmark" / "traffic" / f"{entry['traffic']}.json"
    for path in (config_path, traffic_path):
        if not path.is_file():
            raise HarnessError(f"workload {workload!r}: no file {path}")
    traffic = traffic_mod.load(traffic_path)
    if traffic.chips != int(entry["chips"]):
        raise HarnessError(
            f"workload {workload!r} asks for {entry['chips']} chips, its "
            f"traffic's mesh for {traffic.chips}"
        )
    return Cell(
        name=workload,
        chips=int(entry["chips"]),
        config=json.loads(config_path.read_text()),
        traffic=traffic,
        end_to_end=tuple(m for m in manifest["end_to_end"] if _applies(m, workload)),
        per_layer=tuple(m for m in manifest["per_layer"] if _applies(m, workload)),
        root=root,
    )


def round_of(cell: Cell):
    """The module of the cell's round, by the dotted path its traffic file
    gives: a new kind of round is a new file."""
    try:
        module = importlib.import_module(cell.traffic.round)
    except ImportError as e:
        raise HarnessError(f"cell {cell.name!r}: no round {cell.traffic.round!r}: {e}") from e
    for attr in ("span_names", "Session", "steps", "input_maker"):
        if not hasattr(module, attr):
            raise HarnessError(f"{cell.traffic.round}: a round module needs `{attr}`")
    return module


def span_names(cell: Cell) -> tuple:
    """The spans of the cell's rounds, outermost first: the harness's
    ``round`` and those its round module opens inside; idle gaps are named by
    them and the trace's reader keeps host events of these names."""
    return (ROUND_SPAN, *round_of(cell).span_names)


def load_layers(root) -> dict:
    """Every per-layer metric module under ``benchmark/layers/``, by the
    metric's name. Found by listing the directory: a new metric is a new
    file. Which cells report a metric the manifest alone says (``workloads``);
    a layer file says which of the round's spans it reads (``reads_spans``),
    so that a cell whose round opens no such span is found without a chip, and
    what else it reads (``reads``, of the kinds in :data:`READS`; none if it
    does not say), so that a recorded run that lacks it is known to leave the
    metric silent. A file may have ``before_window(since)``: called in a
    traced run once set-up is over, before the window opens, with
    ``time.perf_counter()`` at the process's start; what it returns reaches
    ``reduce`` as ``LayerContext.before_window[name]``."""
    layers = {}
    directory = pathlib.Path(root) / "benchmark" / "layers"
    for path in sorted(directory.glob("*.py")):
        spec = importlib.util.spec_from_file_location(
            f"benchmark_layer_{path.stem}", path
        )
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        for attr in ("name", "unit", "layer", "moves", "reads_spans", "reduce"):
            if not hasattr(module, attr):
                raise HarnessError(f"{path}: a layer metric needs `{attr}`")
        for read in getattr(module, "reads", ()):
            kind, _, what = read.partition(":")
            if kind not in READS or not what:
                raise HarnessError(f"{path}: reads {read!r}, not a kind of {READS} with a name")
        if module.name in layers:
            raise HarnessError(f"{path}: metric {module.name!r} defined twice")
        layers[module.name] = module
    return layers


def load_peaks(root, device_kind: str) -> dict:
    """The published peaks of this kind of device. A kind that is not in the
    table is an error, not a default."""
    table = json.loads((pathlib.Path(root) / "benchmark" / "peaks.json").read_text())
    try:
        return table["by_device_kind"][device_kind]
    except KeyError:
        raise HarnessError(
            f"no peaks for device kind {device_kind!r} in benchmark/peaks.json"
        ) from None


# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    round: int
    start: float  # time.perf_counter() seconds
    end: float

    @property
    def seconds(self) -> float:
        return self.end - self.start


class Spans:
    """Spans the benchmark's own files put around the calls into each layer.
    Kept in memory; each is also a ``jax.profiler.TraceAnnotation``, so a
    traced run has them on the profiler's clock beside the device's
    operations."""

    def __init__(self):
        self.records: list[Span] = []

    @contextlib.contextmanager
    def __call__(self, name: str, round_index: int):
        import jax

        with jax.profiler.TraceAnnotation(name):
            start = time.perf_counter()
            try:
                yield
            finally:
                self.records.append(
                    Span(name, round_index, start, time.perf_counter())
                )

    def seconds(self, name: str) -> list[float]:
        return [s.seconds for s in self.records if s.name == name]


class CompileCounter:
    """Counts what JAX compiled or loaded from its cache while active, so a
    window that compiled is caught."""

    def __init__(self):
        self.count = 0
        self.active = False
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, *_args, **_kwargs) -> None:
        if self.active and ("backend_compile" in name or "cache_retrieval" in name):
            self.count += 1


# ---------------------------------------------------------------------------
# The window
# ---------------------------------------------------------------------------


def spread(values) -> float:
    """Distance between the quartiles over the median, the quartiles as
    ``statistics.quantiles(values, n=4)`` gives them: the driver's spread
    (numpy's quartiles lie closer together)."""
    if len(values) < 2:
        return 0.0
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def run_cell(
    root,
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    devices,
    process_start: float,
    out_dir=None,
    log=print,
    keep_trace=False,
) -> dict:
    """Set the cell up, run its window, and return the result line as a
    dict. ``process_start`` is ``time.perf_counter()`` at process start;
    ``devices`` are the chips (or, from a test, CPU devices) to run on;
    ``keep_trace`` leaves the profiler's files under ``out_dir`` (the tool
    that records the tests' trace reads them)."""
    cell = load_cell(root, workload)
    layers = load_layers(root)
    for metric in cell.per_layer:
        if metric["name"] not in layers:
            raise HarnessError(f"no layer file defines metric {metric['name']!r}")
    out_dir = pathlib.Path(out_dir) if out_dir else pathlib.Path(root) / "benchmark" / "out"
    out_dir.mkdir(parents=True, exist_ok=True)
    tag = f"{workload}-seed{seed}-trace{int(trace)}"

    round_module = round_of(cell)
    names = span_names(cell)
    compiles = CompileCounter()
    stages = {"to_harness": time.perf_counter() - process_start}  # imports, device
    session = round_module.Session(cell, seed, devices, stages)
    warm = Spans()
    notes = []
    # warm-up is one whole round, epilogue and reconstruct included, so every
    # host path has run once and every program is compiled
    warm_ok, previous = session.run_round(0, warm, subsets=session.warmup_subsets)
    if not warm_ok:
        notes.append("warm-up: aggregate or second clerk subset differed")
    stages["warm_up_round"] = warm.seconds(ROUND_SPAN)[0]

    spans = Spans()
    window = min(seconds, TRACE_WINDOW_SECONDS) if trace else seconds
    # a round whose traced rounds leave more to the profiler than the host
    # holds caps them (``TRACE_WINDOW_ROUNDS``: ``benchmark.rounds``)
    most_rounds = getattr(round_module, "TRACE_WINDOW_ROUNDS", None) if trace else None
    trace_dir = out_dir / f"trace-{tag}"
    before_window = {}
    if trace:
        import jax

        for metric in cell.per_layer:
            hook = getattr(layers[metric["name"]], "before_window", None)
            if hook is not None:
                before_window[metric["name"]] = hook(process_start)

        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)

    attempted = failed = consecutive = repeated = 0
    gc.collect()
    gc.freeze()
    gc.disable()
    compiles.active = True
    try:
        first_start = time.perf_counter()
        while True:
            attempted += 1
            try:
                matched, evidence = session.run_round(attempted, spans)
            except Exception:  # a round that raises is a failed round
                matched, evidence = False, None
                notes.append(traceback.format_exc(limit=4))
            if evidence is not None and previous is not None:
                if np.array_equal(evidence, previous):
                    repeated += 1
            previous = evidence
            if matched:
                consecutive = 0
            else:
                failed += 1
                consecutive += 1
            now = time.perf_counter()
            if (now - first_start >= window or consecutive >= MAX_CONSECUTIVE_FAILURES
                    or attempted == most_rounds):
                break
        last_end = now
    finally:
        compiles.active = False
        gc.enable()
        gc.unfreeze()
        if trace:
            import jax

            jax.profiler.stop_trace()

    if repeated:
        notes.append("two consecutive rounds gave the same evidence (no fresh randomness)")
    if compiles.count:
        notes.append(f"{compiles.count} compilations or cache loads inside the window")
    # every comparison is exact: each number beside its limit, for the line
    # and for the end of the log; after the harness's own four, those the
    # round made of what only it knows
    compared = {
        "warmup_mismatched": {"value": int(not warm_ok), "limit": 0},
        "rounds_mismatched": {"value": failed, "limit": 0},
        "rounds_repeated": {"value": repeated, "limit": 0},
        "compiles_in_window": {"value": compiles.count, "limit": 0},
    }
    for name, c in (session.compared() if hasattr(session, "compared") else {}).items():
        if name in compared:
            raise HarnessError(f"{cell.traffic.round}: `{name}` is the harness's comparison")
        compared[name] = {"value": c["value"], "limit": c["limit"]}
    correct = attempted > 0 and all(c["value"] <= c["limit"] for c in compared.values())

    round_seconds = spans.seconds(ROUND_SPAN)
    finished = attempted - failed
    elapsed = last_end - first_start
    device = _device_line(session)
    log(f"[benchmark] memory of the first chip: {session.devices[0].memory_stats()}")
    # what the harness takes on its own clock; the manifest says which of
    # these the line carries, the run's record keeps them all for the study
    values = {
        "round_s": statistics.median(round_seconds),
        "elems_per_s": finished * cell.elements_per_round / elapsed,
        "setup_s": first_start - process_start,
    }
    result = {
        "correct": correct,
        "attempted": attempted,
        "failed": failed,
        "metrics": {},
        "device": device,
    }
    record = {
        "workload": workload,
        "seed": seed,
        "seconds": seconds,
        "trace": bool(trace),
        "device": device,
        "round_s_each": round_seconds,
        "round_start_s_each": [
            s.start - first_start for s in spans.records if s.name == ROUND_SPAN
        ],
        "round_spread": spread(round_seconds),
        "window_s": elapsed,
        "warmup_round_s": warm.seconds(ROUND_SPAN),
        "spans": {n: spans.seconds(n) for n in names if n != ROUND_SPAN},
        "setup_stages_s": stages,
        "notes": notes,
        **values,
    }

    if trace:
        told = dict(
            name=cell.name,
            chips=cell.chips,
            config=cell.config,
            traffic=cell.traffic,
            rounds=attempted,
            elements_per_round=cell.elements_per_round,
            chunk_bytes=session.chunk_bytes,
            acc_bytes=session.acc_bytes,
            steps_per_round=session.steps_per_round,
            plan=session.plan,
            memory_peak_bytes=device["memory_peak_bytes"],
            log=log,
        )
        used = session.devices
        del session  # the resident input goes before the step is compiled again
        raw, reduced = _read_trace(trace_dir, names, log)
        report = chunk_step = None
        if reduced is not None:
            # the chunk step is the first of the round's programs
            programs = round_module.steps(cell, used)
            chunk_step = scopes.join_table(programs[:1])
            report = scopes.split(
                raw, {**chunk_step, **scopes.join_table(programs[1:])}, names
            )
            if report["absent"]:
                log(
                    f"[benchmark] {len(report['absent'])} operations of the step's module "
                    "are not in its compiled text: no scope metric is reported"
                )
        context = LayerContext(
            peaks=load_peaks(root, device["kind"]) if reduced is not None else None,
            scopes=report if report and not report["absent"] else None,
            host_spans=report["host_spans_s"] if report else None,
            chunk_step_modules=(
                frozenset(name.split("/", 1)[0] for name in chunk_step) if chunk_step else None
            ),
            before_window=before_window,
            **told,
        )
        for metric in cell.per_layer:
            value = layers[metric["name"]].reduce(spans.records, reduced, context)
            if value is not None:
                result["metrics"][metric["name"]] = {
                    "value": float(value), "unit": metric["unit"],
                }
        if reduced is not None:
            device["busy_s"] = reduced.mean_busy_seconds()
            device["window_s"] = reduced.window_seconds
            result["breakdown"] = {
                "device_ops": reduced.top_operations(10),
                "idle_gaps": reduced.idle_gaps_by_span(10),
            }
        record["per_layer"] = result["metrics"]
        record["breakdown"] = result.get("breakdown")
        record["scopes"] = report
        if not keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        for metric in cell.end_to_end:
            if metric["name"] not in values:
                raise HarnessError(f"the harness takes no metric {metric['name']!r}")
            result["metrics"][metric["name"]] = {
                "value": values[metric["name"]], "unit": metric["unit"],
            }
    result["compared"] = record["compared"] = compared

    (out_dir / f"rounds-{tag}.json").write_text(json.dumps(record, indent=1))
    for note in notes:
        log(f"[benchmark] {note}")
    log(
        f"[benchmark] {workload}: {attempted} rounds, {failed} failed, "
        f"round_s median {values['round_s']:.4f} (spread inside the run "
        f"{100 * record['round_spread']:.2f}%), set-up {values['setup_s']:.1f} s"
    )
    for name, c in compared.items():
        log(f"[benchmark] compared {name}: {c['value']} (limit {c['limit']})")
    return result


@dataclasses.dataclass(frozen=True)
class LayerContext:
    """What a layer metric's ``reduce`` is told about the cell and the run."""

    name: str
    chips: int
    config: dict
    traffic: traffic_mod.Traffic
    rounds: int  # rounds in the window
    elements_per_round: int  # participants x dim
    chunk_bytes: int  # input bytes one chunk step reads, all chips together
    acc_bytes: int  # bytes a chunk step carries in and hands on, on one chip
    steps_per_round: int
    plan: object  # the program's AggregationPlan
    peaks: dict | None  # this device kind's row of benchmark/peaks.json
    memory_peak_bytes: int
    log: object
    #: ``scopes.split``'s report of the traced window (seconds a round by the
    #: program's ``fabric.*`` scopes on each chip, ``unscoped``, ...); ``None``
    #: with no device plane, or where an operation of the step was ``absent``
    #: from its compiled text, so that no scope metric is reported
    scopes: dict | None = None
    #: the program's own host spans (``telemetry.span``), by name: seconds a
    #: round, median over the window's rounds; ``None`` with no device plane
    host_spans: dict | None = None
    #: the names the trace gives the chunk step's program(s) (``jit_step``):
    #: the module(s) of the first of the round's ``steps``, from its compiled
    #: text; ``None`` with no device plane
    chunk_step_modules: frozenset | None = None
    #: what each listed metric's ``before_window`` returned, by the metric's
    #: name (``load_layers``); a metric with no such hook is not in it
    before_window: dict = dataclasses.field(default_factory=dict)


def _device_line(session) -> dict:
    import jax

    first = session.devices[0]
    return {
        "platform": first.platform,
        "kind": first.device_kind,
        "count": jax.device_count(),
        "memory_peak_bytes": session.memory_peak_bytes(),
    }


def _read_trace(trace_dir: pathlib.Path, names, log) -> tuple:
    """``(raw, reduced)``: the profiler's trace of the window as the plain
    structure, and reduced by the spans ``names``; ``reduced`` is ``None``
    where the trace holds no device plane (a CPU rehearsal), so that trace
    metrics are left out of the line rather than invented."""
    files = sorted(trace_dir.rglob("*.xplane.pb"))
    if not files:
        log("[benchmark] the profiler wrote no trace")
        return None, None
    raw = scopes.load(files[-1], names)
    reduced = trace_reduce.reduce(raw, names)
    if reduced is None:
        log("[benchmark] the trace holds no device plane: trace metrics left out")
    return raw, reduced
