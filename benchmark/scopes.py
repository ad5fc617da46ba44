"""Seconds by scope: one traced run of a cell, split by the names the program
gives its own work.

    python benchmark/scopes.py --workload <name> --seed <n> [--seconds <s>]

The program names its device work with ``jax.named_scope`` (``fabric.rand``
⊃ ``draw``, ``limb_sum``; ...) and its host work with ``telemetry.span``
(``fabric.epilogue.recombine``, ...), which in a process that holds JAX is
also an annotation of the profiler's trace. This reader turns one
``.xplane.pb`` into a report (:func:`split`). The harness makes it in every
traced run and hands it to the layer files (``LayerContext.scopes``,
``.host_spans``), so a manifest metric and this command read one reduction.
Inside the window of the harness's ``round`` annotations and per round:

* per chip, self seconds (``trace_reduce.self_times``) by outer scope and by
  path, and the ``unscoped`` rest by operation name and as a share of busy
  time. Scoped plus unscoped is the chip's busy time;
* ``absent``: the operations of the step's module that its text does not
  hold. The join failed for them, so they are no honest ``unscoped``;
* host seconds by ``fabric.*`` span (median over the rounds);
* the idlest chip's idle seconds by the innermost program span the host was
  in, then by the harness's span, then ``-``.

Where an operation's scope is found (read by hand on a v5e, jax 0.9.0): an
``XLA Ops`` event carries its whole HLO line as its name and three timing
stats, no ``op_name``; the scope is in the executable's metadata only. So
the operation's name is joined with the text of the round's step(s) (what
the cell's round module gives as ``steps``), compiled in this process with
the compile cache off: JAX's cache key strips
debug info, scopes are debug info, and an executable loaded from a cache the
parent commit filled names nothing. Instruction names do not depend on debug
info, so the run itself may use the cache.

Rules: an operation counts under the ``fabric.*`` part of its ``op_name``,
cut after the component that follows the outer scope (an inner scope where
the program has one, the primitive's name where it has none). A fused
operation carries its root's (one kernel is reported whole). A fusion whose
root the compiler made, with no metadata of its own, counts under what most
of its instructions carry: a majority of instructions, not of time, which the
trace does not give inside a kernel. An operation that the text holds with no
``fabric.*`` in its ``op_name`` is ``unscoped``: the harness's own ``acc +
out``, loops the compiler builds itself with no metadata at all, and every
operation of another module than the step's.

The text is of a second compile of the step, made here after the run, not of
the executable that was timed: were the two numbered differently, seconds
would go to the wrong scope. What the trace can show of that it does: an
operation of the step's module whose name the text does not hold is
``absent``, and one such fails the run.

``main`` runs the cell traced through ``harness.run_cell`` on the chips
``run.acquire_chips`` gives, prints the run's report as one JSON line, and
exits 1 where an operation is ``absent`` or ``unscoped`` is over half of busy
time: the join failed or the program lost its scopes, and the split says
nothing.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse
import collections
import json
import pathlib
import re
import statistics
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import trace_reduce
from benchmark.trace_reduce import NS, merge, self_times, subtract

#: what the program's own names start with, scopes and spans alike
PROGRAM_PREFIX = "fabric."
UNSCOPED = "unscoped"
EXIT_UNSCOPED = 1

_INSTRUCTION = re.compile(r"\s*(?:ROOT )?%?([^\s=]+) = ")
_COMPUTATION = re.compile(r"(?:ENTRY )?%?([^\s(]+) \(.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([^\s,)}]+)")


# ---------------------------------------------------------------------------
# From an executable's text to a path for each instruction
# ---------------------------------------------------------------------------


def scope_path(op_name: str | None) -> str | None:
    """``jit(step)/fabric.rand/draw/jit(remainder)/rem`` -> ``fabric.rand/draw``;
    ``None`` for an ``op_name`` with no program scope in it."""
    parts = (op_name or "").split("/")
    for i, part in enumerate(parts):
        if part.startswith(PROGRAM_PREFIX):
            return "/".join(parts[i : i + 2])
    return None


def op_paths(hlo_text: str) -> dict:
    """``{"<module>/<instruction>": path or None}`` for every instruction of
    a compiled module's text (``compiled.as_text()``), keyed as
    ``trace_reduce`` names a device operation."""
    module = re.search(r"HloModule ([^\s,]+)", hlo_text)
    if module is None:
        raise ValueError("not an HLO module's text")
    own = {}  # instruction -> its own op_name, or None
    calls = {}  # fusion instruction -> the computation it calls
    inside = collections.defaultdict(list)  # computation -> its instructions' op_names
    computation = None
    for line in hlo_text.splitlines():
        header = _COMPUTATION.match(line)
        if header:
            computation = header.group(1)
            continue
        instruction = _INSTRUCTION.match(line)
        if not instruction or computation is None:
            continue
        name = instruction.group(1)
        op_name = _OP_NAME.search(line)
        own[name] = op_name.group(1) if op_name else None
        inside[computation].append(own[name])
        called = _CALLS.search(line)
        if called and " fusion(" in line:
            calls[name] = called.group(1)
    paths = {}
    for name, op_name in own.items():
        if op_name is None and name in calls:
            # a root the compiler made: what most of the fusion's
            # instructions carry, those with no metadata not counted
            votes = collections.Counter(
                scope_path(n) for n in inside[calls[name]] if n is not None
            )
            path = votes.most_common(1)[0][0] if votes else None
        else:
            path = scope_path(op_name)
        paths[f"{module.group(1)}/{name}"] = path
    return paths


# ---------------------------------------------------------------------------
# From a trace to seconds by scope
# ---------------------------------------------------------------------------


def load(path, span_names) -> dict:
    """A profiler ``.xplane.pb`` as ``trace_reduce``'s plain structure: of
    the host's events the spans named, and every one of the program's."""
    return trace_reduce.load_xplane(path, span_names, (PROGRAM_PREFIX,))


def _seconds(intervals) -> float:
    return sum(e - s for s, e in intervals) * NS


def split(raw: dict, paths: dict, span_names) -> dict | None:
    """The plain structure of one traced window, the join table of its
    step(s) and the harness's span names (``round`` first) -> the report
    ``main`` prints; ``None`` where the trace holds no device plane. Seconds
    are per round of the window, except the idle seconds, which are the
    window's."""
    reduced = trace_reduce.reduce(raw, span_names, (PROGRAM_PREFIX,))
    if reduced is None:
        return None
    program_spans = sorted(
        {n for n, _s, _e in reduced.host_spans if n.startswith(PROGRAM_PREFIX)}
    )
    rounds = sorted((s, e) for n, s, e in reduced.host_spans if n == "round")
    per_round = 1.0 / max(len(rounds), 1)
    joined_modules = {name.split("/", 1)[0] for name in paths}

    chips, absent = {}, set()
    for chip in reduced.chips:
        by_path, by_name = collections.Counter(), collections.Counter()
        for name, own in self_times(reduced.ops[chip]):
            if name not in paths and name.split("/", 1)[0] in joined_modules:
                absent.add(name)
            path = paths.get(name)
            if path is None:
                by_name[name] += own
            else:
                by_path[path] += own
        by_scope = collections.Counter()
        for path, own in by_path.items():
            by_scope[path.split("/")[0]] += own
        busy = reduced.busy_seconds(chip)
        unscoped = sum(by_name.values()) * NS
        chips[chip] = {
            "busy_s": busy * per_round,
            "by_scope": {k: v * NS * per_round for k, v in by_scope.most_common()},
            "by_path": {k: v * NS * per_round for k, v in by_path.most_common()},
            UNSCOPED: {
                "s": unscoped * per_round,
                "share_of_busy": unscoped / busy if busy else 0.0,
                "by_name": [[k, v * NS * per_round] for k, v in by_name.most_common(30)],
            },
        }

    host = {}
    for name in program_spans:
        each = [
            sum(e - s for n, s, e in reduced.host_spans if n == name and start <= s < end)
            for start, end in rounds
        ]
        host[name] = statistics.median(each) * NS if each else 0.0

    idlest = reduced.idlest_chip()
    gaps = subtract([list(reduced.window)], merge((s, e) for _n, s, e in reduced.ops[idlest]))
    idle = unnamed = _seconds(gaps)
    by_span = collections.Counter()
    # innermost first: among spans that nest the shorter is the inner one;
    # the program's spans before the harness's, whatever their lengths
    for names in (set(program_spans), set(span_names)):
        spans = [(e - s, s, e, n) for n, s, e in reduced.host_spans if n in names]
        for _length, s, e, name in sorted(spans):
            gaps = subtract(gaps, [[s, e]])
            left = _seconds(gaps)
            by_span[name] += unnamed - left
            unnamed = left
    by_span["-"] = unnamed
    in_program = sum(v for k, v in by_span.items() if k.startswith(PROGRAM_PREFIX))
    busiest = max(reduced.chips, key=reduced.busy_seconds)
    return {
        "rounds": len(rounds),
        "window_s": reduced.window_seconds,
        "busiest_chip": busiest,
        "chips": chips,
        "absent": sorted(absent),
        "host_spans_s": host,
        "idle": {
            "chip": idlest,
            "s": idle,
            "by_span": [[k, v] for k, v in by_span.most_common() if v > 0],
            "in_program_spans_share": in_program / idle if idle else 0.0,
        },
    }


# ---------------------------------------------------------------------------
# One cell, traced
# ---------------------------------------------------------------------------


def scope_seconds(report: dict | None, scope: str) -> float | None:
    """Seconds a round under ``scope`` (``fabric.rand``, or ``unscoped``) on
    the busiest chip; ``None`` where there is no report or nothing ran under
    it. What a layer file reads."""
    if report is None:
        return None
    chip = report["chips"][report["busiest_chip"]]
    if scope == UNSCOPED:
        return chip[UNSCOPED]["s"] or None
    return chip["by_scope"].get(scope)


def step_text(jitted, args) -> str:
    """The text of one jitted program as compiled here for the devices its
    example arguments are placed on, with its metadata: compiled anew, the
    compile cache off (module doc)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return jitted.lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()


def join_table(steps) -> dict:
    """:func:`op_paths` of every program a round runs in the window (a round
    module's ``steps(cell, devices)``), in one table."""
    paths = {}
    for jitted, args in steps:
        paths.update(op_paths(step_text(jitted, args)))
    return paths


def trace_cell(root, workload: str, seed: int, seconds: float, devices, log):
    """Run the cell traced through the harness. Returns ``(line, report)``:
    the harness's result line and the report it made of the same window
    (``None`` where the trace held no device plane)."""
    from benchmark import harness

    with tempfile.TemporaryDirectory() as tmp:
        line = harness.run_cell(
            root, workload, seed, seconds, True, devices, PROCESS_START, out_dir=tmp, log=log
        )
        record = json.loads(
            (pathlib.Path(tmp) / f"rounds-{workload}-seed{seed}-trace1.json").read_text()
        )
    return line, record["scopes"]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=12.0)
    args = parser.parse_args(argv)

    from benchmark import harness, run

    devices = run.acquire_chips(harness.load_cell(ROOT, args.workload).chips)

    import jax
    from sda_tpu.ops.jaxcfg import ensure_x64

    ensure_x64()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    line, report = trace_cell(ROOT, args.workload, args.seed, args.seconds, devices, run.log)
    if report is None:
        run.log("[scopes] the trace holds no device plane. No result.")
        return run.EXIT_NO_DEVICE
    report = {
        "workload": args.workload,
        "correct": line["correct"],
        "device": line["device"],
        "metrics": {name: m["value"] for name, m in line["metrics"].items()},
        **report,
    }
    print(json.dumps(report), flush=True)
    if report["absent"]:
        run.log(
            f"[scopes] {len(report['absent'])} operations of the step's module are not in "
            f"its compiled text, so their seconds have no scope: {report['absent'][:10]}"
        )
        return EXIT_UNSCOPED
    share = report["chips"][str(report["busiest_chip"])][UNSCOPED]["share_of_busy"]
    if share > 0.5:
        run.log(
            f"[scopes] {100 * share:.0f} % of busy time is unscoped: the join on "
            "operation names failed, or the program has lost its scopes"
        )
        return EXIT_UNSCOPED
    return 0


if __name__ == "__main__":
    sys.exit(main())
