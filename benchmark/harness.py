"""The benchmark's harness: finds a cell's files by the names in
``BENCHMARK.json``, sets the cell up on the devices it is handed, runs rounds
back to back for the window, and reduces what it saw to the result line.

It knows no cell, configuration, traffic mix or metric by name:

* a cell is one entry of ``BENCHMARK.json``'s ``workloads``;
* its configuration is ``benchmark/configs/<config>.json`` (field, scheme,
  dim, dropped clerks, guarantees);
* its traffic mix is ``benchmark/traffic/<traffic>.json`` (engine entry by
  dotted name, rows, passes, chunk, mesh), read by the one generator in
  :mod:`benchmark.traffic`;
* a per-layer metric is one module in ``benchmark/layers/``, found by listing
  the directory.

One round (the clock runs from key to comparison): fresh share key -> the
engine's chunk step over every chunk of the resident input, with the
program's default share randomness -> ``block_until_ready`` and transfer of
the accumulator -> host epilogue to clerk sums -> drop the configuration's
clerks -> reconstruct from exactly ``reconstruction_threshold`` survivors ->
compare the whole aggregate with the plain reference.

The devices are handed in by the caller: ``run.py`` hands in TPU chips or
exits, the tests hand in CPU devices. Nothing here picks a platform.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import pathlib
import shutil
import statistics
import time
import traceback

import numpy as np

from benchmark import reference, trace_reduce
from benchmark import traffic as traffic_mod

#: the benchmark's span names, outermost first; idle gaps are named by them
SPAN_NAMES = ("round", "dispatch", "fold", "fetch", "epilogue", "check")

#: a traced window closes after this many seconds (or ``--seconds``, if
#: shorter): traces are large and tracing slows the host, so the per-layer
#: run is short and the end-to-end numbers come from the untraced run
TRACE_WINDOW_SECONDS = 12.0

#: a window that fails this many rounds in a row stops
MAX_CONSECUTIVE_FAILURES = 3


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


def load_layers(root) -> dict:
    """Every per-layer metric module under ``benchmark/layers/``, by the
    metric's name. Found by listing the directory: a new metric is a new
    file."""
    layers = {}
    directory = pathlib.Path(root) / "benchmark" / "layers"
    for path in sorted(directory.glob("*.py")):
        spec = importlib.util.spec_from_file_location(
            f"benchmark_layer_{path.stem}", path
        )
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        for attr in ("name", "unit", "layer", "moves", "cells", "reduce"):
            if not hasattr(module, attr):
                raise HarnessError(f"{path}: a layer metric needs `{attr}`")
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


# ---------------------------------------------------------------------------
# Set-up
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Program:
    """What a cell runs, built from its files: the program's plan and scheme,
    the jitted chunk step, the host epilogue and reconstruct. Holds no array,
    so the compile rehearsal builds it for described devices too."""

    scheme: object
    plan: object
    modulus: int
    chunk_fn: object  # fn(secrets, key) -> accumulator, one chunk
    step: object  # jitted fn(acc, chunk, key, i) -> acc
    epilogue: object  # fn(acc_host) -> (n, B) clerk sums
    reconstruct: object
    survivors: list  # exactly reconstruction_threshold surviving clerks
    second_subset: list  # warm-up's second subset: another clerk left out


def build_program(cell: Cell, mesh) -> Program:
    import jax

    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp
    from jax import lax

    from sda_tpu.ops import find_packed_parameters
    from sda_tpu.parallel.engine import make_plan
    from sda_tpu.protocol import PackedShamirSharing

    spec = cell.config["scheme"]
    if spec["kind"] != "packed_shamir":
        raise HarnessError(f"unknown scheme kind {spec['kind']!r}")
    k, t, n = spec["secret_count"], spec["privacy_threshold"], spec["share_count"]
    p, w2, w3 = find_packed_parameters(
        k, t, n, min_modulus_bits=spec["min_modulus_bits"], seed=spec["parameter_seed"]
    )
    scheme = PackedShamirSharing(k, n, t, p, w2, w3)
    modulus = int(p)
    plan = make_plan(scheme, cell.dim)
    threshold = scheme.reconstruction_threshold
    stated = cell.config["guarantees"]
    if stated["reconstruction_threshold"] != threshold or stated["privacy_threshold"] != t:
        raise HarnessError("the configuration's stated thresholds are not the scheme's")
    dropped = set(cell.config["dropped_clerks"])
    alive = [i for i in range(n) if i not in dropped]
    if len(alive) < threshold:
        raise HarnessError("fewer clerks survive than reconstruction needs")
    survivors = alive[:threshold]

    tr = cell.traffic
    chunk_fn = traffic_mod.resolve(tr.engine_call)(
        traffic_mod.resolve(tr.engine), plan, mesh
    )
    accumulate_mod_p = tr.accumulate == "sum_mod_p"

    def step(acc, chunk, key, i):
        # one chunk step: a chunk of the resident input, the round's key with
        # the step's number folded in, the program's default share randomness
        out = chunk_fn(chunk, jax.random.fold_in(key, i))
        acc = acc + out
        if accumulate_mod_p:
            acc = lax.rem(acc, jnp.int64(modulus))
        return acc

    return Program(
        scheme=scheme,
        plan=plan,
        modulus=modulus,
        chunk_fn=chunk_fn,
        step=jax.jit(step),
        epilogue=traffic_mod.resolve(tr.epilogue_call)(
            traffic_mod.resolve(tr.epilogue), plan
        ),
        reconstruct=traffic_mod.resolve(tr.reconstruct),
        survivors=survivors,
        second_subset=[i for i in range(n) if i != survivors[-1]][:threshold],
    )


class Session:
    """One cell set up on its devices: input and reference resident, the
    chunk step ready, to run rounds."""

    def __init__(self, cell: Cell, seed: int, devices, stages=None):
        """``stages``, if given, is filled with the seconds each part of
        set-up took, for the run's record."""
        import jax
        import jax.numpy as jnp

        clock = time.perf_counter()
        stages = {} if stages is None else stages

        def stage(name):
            nonlocal clock
            now = time.perf_counter()
            stages[name] = now - clock
            clock = now

        if len(devices) < cell.chips:
            raise HarnessError(
                f"cell {cell.name!r} needs {cell.chips} devices, got {len(devices)}"
            )
        self.cell = cell
        self.devices = list(devices[: cell.chips])
        tr = cell.traffic
        self.mesh = traffic_mod.make_mesh(tr, self.devices)
        self.program = program = build_program(cell, self.mesh)
        self.plan, self.modulus = program.plan, program.modulus
        modulus = program.modulus
        steps = tr.steps_per_pass

        # everything a step takes besides its chunk sits on every chip before
        # the window, so that a step moves nothing between chips but its psum
        everywhere = traffic_mod.replicated(self.devices, self.mesh)
        self.step_index = [
            jax.device_put(jnp.int32(i), everywhere) for i in range(steps * tr.passes)
        ]
        self.fold_in = jax.jit(jax.random.fold_in, out_shardings=everywhere)
        # the input and the reference's sums of it, made on the device from
        # the seed by one program, chunk by chunk
        stage("program")
        make = traffic_mod.chunk_maker(tr, cell.dim, modulus, self.devices, self.mesh)
        seed_key = jax.random.key(seed)
        input_key = self.fold_in(seed_key, 0)
        self.share_key = self.fold_in(seed_key, 1)
        half_sums = jax.device_put(jnp.zeros((2, cell.dim), jnp.int64), everywhere)
        self.chunks, columns = [], []
        for i in self.step_index[:steps]:
            chunk, half_sums, strided = make(input_key, i, half_sums)
            self.chunks.append(chunk)
            columns.append(strided)
        half_sums, columns = np.asarray(half_sums), [np.asarray(c) for c in columns]
        stage("input_on_device")
        self.want = reference.aggregate(
            half_sums, np.concatenate(columns), modulus, tr.passes, tr.rows
        )
        stage("reference_on_host")
        acc_shape = jax.eval_shape(program.chunk_fn, self.chunks[0], self.share_key)
        self.zero_acc = jax.device_put(jnp.zeros(acc_shape.shape, jnp.int64), everywhere)
        self.chunk_bytes = int(self.chunks[0].nbytes)

    def run_round(self, index: int, spans: Spans, subsets=None):
        """One round. Returns ``(matched, clerk_sums)``; ``subsets`` (warm-up
        only) are further clerk subsets that must reveal the same."""
        with spans("round", index):
            key = self.fold_in(self.share_key, index)
            with spans("dispatch", index):
                acc = self.zero_acc
                for i, step_number in enumerate(self.step_index):
                    chunk = self.chunks[i % len(self.chunks)]  # passes wrap
                    acc = self.program.step(acc, chunk, key, step_number)
            with spans("fold", index):
                acc.block_until_ready()
            with spans("fetch", index):
                acc_host = np.asarray(acc)
            with spans("epilogue", index):
                clerk_sums = np.asarray(self.program.epilogue(acc_host))
                got = self._reveal(clerk_sums, self.program.survivors)
            with spans("check", index):
                matched = bool(np.array_equal(got, self.want))
        for subset in subsets or ():
            matched = matched and bool(
                np.array_equal(self._reveal(clerk_sums, subset), self.want)
            )
        return matched, clerk_sums

    def _reveal(self, clerk_sums, subset):
        out = self.program.reconstruct(clerk_sums, subset, self.program.scheme, self.cell.dim)
        return np.mod(np.asarray(out).astype(np.int64), self.modulus)

    def memory_peak_bytes(self) -> int:
        """The peak on the fullest of the cell's chips (0 where the backend
        reports none, as the CPU does)."""
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in self.devices]
        return int(max(peaks))


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
    """Distance between the quartiles over the median."""
    if len(values) < 2:
        return 0.0
    q = statistics.quantiles(values, n=4, method="inclusive")
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

    compiles = CompileCounter()
    stages = {"to_harness": time.perf_counter() - process_start}  # imports, device
    session = Session(cell, seed, devices, stages)
    warm = Spans()
    notes = []
    # warm-up is one whole round, epilogue and reconstruct included, so every
    # host path has run once and every program is compiled
    warm_ok, previous = session.run_round(0, warm, subsets=[session.program.second_subset])
    if not warm_ok:
        notes.append("warm-up: aggregate or second clerk subset differed")
    stages["warm_up_round"] = warm.seconds("round")[0]

    spans = Spans()
    window = min(seconds, TRACE_WINDOW_SECONDS) if trace else seconds
    trace_dir = out_dir / f"trace-{tag}"
    if trace:
        import jax

        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)

    attempted = failed = consecutive = 0
    rounds_differ = True
    gc.collect()
    gc.freeze()
    gc.disable()
    compiles.active = True
    try:
        first_start = time.perf_counter()
        while True:
            attempted += 1
            try:
                matched, clerk_sums = session.run_round(attempted, spans)
            except Exception:  # a round that raises is a failed round
                matched, clerk_sums = False, None
                notes.append(traceback.format_exc(limit=4))
            if clerk_sums is not None and previous is not None:
                if np.array_equal(clerk_sums, previous):
                    rounds_differ = False
            previous = clerk_sums
            if matched:
                consecutive = 0
            else:
                failed += 1
                consecutive += 1
            now = time.perf_counter()
            if now - first_start >= window or consecutive >= MAX_CONSECUTIVE_FAILURES:
                break
        last_end = now
    finally:
        compiles.active = False
        gc.enable()
        gc.unfreeze()
        if trace:
            import jax

            jax.profiler.stop_trace()

    if not rounds_differ:
        notes.append("two consecutive rounds gave the same clerk sums")
    if compiles.count:
        notes.append(f"{compiles.count} compilations or cache loads inside the window")
    correct = (
        warm_ok and failed == 0 and rounds_differ and compiles.count == 0 and attempted > 0
    )

    round_seconds = spans.seconds("round")
    finished = attempted - failed
    elapsed = last_end - first_start
    device = _device_line(session)
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
            s.start - first_start for s in spans.records if s.name == "round"
        ],
        "round_spread": spread(round_seconds),
        "window_s": elapsed,
        "warmup_round_s": warm.seconds("round"),
        "spans": {n: spans.seconds(n) for n in SPAN_NAMES if n != "round"},
        "setup_stages_s": stages,
        "notes": notes,
        **values,
    }

    if trace:
        reduced = _reduce_trace(trace_dir, log)
        context = LayerContext(
            name=cell.name,
            chips=cell.chips,
            config=cell.config,
            traffic=cell.traffic,
            rounds=attempted,
            elements_per_round=cell.elements_per_round,
            chunk_bytes=session.chunk_bytes,
            acc_bytes=int(session.zero_acc.nbytes),
            steps_per_round=len(session.step_index),
            plan=session.plan,
            peaks=load_peaks(root, device["kind"]) if reduced is not None else None,
            memory_peak_bytes=device["memory_peak_bytes"],
            log=log,
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
        if not keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        for metric in cell.end_to_end:
            if metric["name"] not in values:
                raise HarnessError(f"the harness takes no metric {metric['name']!r}")
            result["metrics"][metric["name"]] = {
                "value": values[metric["name"]], "unit": metric["unit"],
            }

    (out_dir / f"rounds-{tag}.json").write_text(json.dumps(record, indent=1))
    for note in notes:
        log(f"[benchmark] {note}")
    log(f"[benchmark] memory of the first chip: {session.devices[0].memory_stats()}")
    log(
        f"[benchmark] {workload}: {attempted} rounds, {failed} failed, "
        f"round_s median {values['round_s']:.4f} (spread inside the run "
        f"{100 * record['round_spread']:.2f}%), set-up {values['setup_s']:.1f} s"
    )
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
    acc_bytes: int  # bytes of the accumulator a step carries
    steps_per_round: int
    plan: object  # the program's AggregationPlan
    peaks: dict | None  # this device kind's row of benchmark/peaks.json
    memory_peak_bytes: int
    log: object


def _device_line(session: Session) -> dict:
    import jax

    first = session.devices[0]
    return {
        "platform": first.platform,
        "kind": first.device_kind,
        "count": jax.device_count(),
        "memory_peak_bytes": session.memory_peak_bytes(),
    }


def _reduce_trace(trace_dir: pathlib.Path, log):
    """The profiler's trace of the window, reduced; ``None`` where the trace
    holds no device plane (a CPU rehearsal), so that trace metrics are left
    out of the line rather than invented."""
    files = sorted(trace_dir.rglob("*.xplane.pb"))
    if not files:
        log("[benchmark] the profiler wrote no trace")
        return None
    raw = trace_reduce.load_xplane(files[-1], host_names=SPAN_NAMES)
    reduced = trace_reduce.reduce(raw, SPAN_NAMES)
    if reduced is None:
        log("[benchmark] the trace holds no device plane: trace metrics left out")
    return reduced
