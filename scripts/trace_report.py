#!/usr/bin/env python
"""Round flight recorder CLI: render one trace's timeline, or one interval
of the monotonic clock, from a banked artifact.

Input is anything that carries span records in the SpanLog shape
({name, trace_id, start, attrs, start_mono, duration_s}):

- a soak artifact (``bench-artifacts/soak-<stamp>.json``, which embeds
  the span ring and per-round trace ids),
- a ``/v1/metrics.json`` or ``telemetry.snapshot()`` saved to a file
  (``chiprun_out/timeline-<tag>.json``, from ``scripts/setup_events.py``),
- or a bare JSON array of span records.

Picks the trace to render by ``--trace``, else the artifact's last
round's trace id (soak artifacts), else the trace with the most spans —
then prints the stage waterfall, overlap efficiency, and critical path,
and (with ``--out``) writes Chrome trace-event JSON loadable in
``chrome://tracing`` or https://ui.perfetto.dev.

With ``--since`` / ``--until`` (seconds of ``time.perf_counter()``, as the
records' ``start_mono``), or ``--between <span name>`` (from the first record
of that name to the end of the last), no trace id is asked for (the fabric's
spans and JAX's events carry none): whatever started in the interval is
reported by span name, with count, seconds and own seconds (less what nests
inside), then overlap efficiency and critical path.

Usage:
  python scripts/trace_report.py soak-xyz.json               # report
  python scripts/trace_report.py soak-xyz.json --list        # traces in file
  python scripts/trace_report.py soak-xyz.json --trace t1 --out round.trace.json
  python scripts/trace_report.py chiprun_out/timeline-x.json --between fabric.feed
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from sda_tpu.telemetry import flight  # noqa: E402


def extract_spans(doc):
    """Span records from any supported artifact shape."""
    if isinstance(doc, list):
        return doc
    if isinstance(doc, dict):
        spans = doc.get("spans")
        if isinstance(spans, list):
            return spans
    return []


def default_trace(doc, spans):
    """The trace worth looking at when --trace is absent: the last soak
    round's id if recorded, else the busiest trace in the span list."""
    if isinstance(doc, dict):
        rounds = doc.get("rounds")
        if isinstance(rounds, list):
            for r in reversed(rounds):
                if isinstance(r, dict) and r.get("trace_id"):
                    if any(s.get("trace_id") == r["trace_id"] for s in spans):
                        return r["trace_id"]
    traces = flight.traces_in(spans)
    if not traces:
        return None
    return max(traces, key=lambda t: t["spans"])["trace_id"]


def print_report(trace_id: str, spans: list) -> None:
    report = flight.round_report(spans)
    print(f"trace {trace_id}: {report['spans']} spans, "
          f"wall {report['wall_s'] * 1000:.1f} ms, "
          f"busy {report['busy_s'] * 1000:.1f} ms, "
          f"span-sum {report['span_s'] * 1000:.1f} ms, "
          f"overlap efficiency {report['overlap_efficiency']:.2f}")

    print("\nstage waterfall (offset-ordered; bar spans offset..offset+busy):")
    wall = report["wall_s"] or 1e-9
    width = 40
    print(f"{'stage':>12} {'spans':>5} {'offset_ms':>10} {'busy_ms':>9} "
          f"{'share':>6}  timeline")
    for row in report["stages"]:
        lo = int(width * row["offset_s"] / wall)
        # draw the stage's busy time from its first start; clamp into frame
        ln = max(1, int(width * row["busy_s"] / wall))
        lo = min(lo, width - 1)
        bar = " " * lo + "#" * min(ln, width - lo)
        print(f"{row['stage']:>12} {row['spans']:>5} "
              f"{row['offset_s'] * 1000:>10.1f} {row['busy_s'] * 1000:>9.1f} "
              f"{row['share']:>6.2f}  |{bar:<{width}}|")

    if report.get("tier_close"):
        print("\ntier close levels (dispatch mode / lane occupancy):")
        for row in report["tier_close"]:
            eff = ("-" if row["overlap_efficiency"] is None
                   else f"{row['overlap_efficiency']:.2f}")
            print(f"  tier {row['tier']}: {row['nodes']} nodes, "
                  f"mode={row['mode']} width={row['width']} "
                  f"overlap={eff} in {row['duration_s'] * 1000:.1f} ms")

    print("\ncritical path (the span holding the wall clock at each moment):")
    for hop in report["critical_path"]:
        print(f"  +{hop['offset_s'] * 1000:>9.1f} ms  "
              f"{hop['name']:<24} {hop['duration_s'] * 1000:>9.1f} ms")


def print_interval(title: str, report: dict, file=None, hops: int = 12) -> None:
    """``flight.interval_report``'s numbers: by span name the whole interval
    and, where the report has rounds, the median round."""
    print(f"{title}: {report['spans']} spans, wall {report['wall_s']:.3f} s, "
          f"busy {report['busy_s']:.3f} s, span-sum {report['span_s']:.3f} s, "
          f"overlap efficiency {report['overlap_efficiency']:.2f}", file=file)
    a_round = report.get("a_round")
    head = f"{'span':>36} {'count':>6} {'seconds':>10} {'own_s':>10}"
    if a_round is not None:
        head += f"   a round ({len(report['rounds'])}): {'count':>6} {'seconds':>10} {'own_s':>10}"
    print(head, file=file)
    for name, row in report["names"].items():
        line = f"{name:>36} {row['count']:>6} {row['seconds']:>10.4f} {row['own_s']:>10.4f}"
        if a_round is not None and name in a_round:
            r = a_round[name]
            line += f"{'':>16}{r['count']:>6g} {r['seconds']:>10.5f} {r['own_s']:>10.5f}"
        print(line, file=file)
    path = report["critical_path"]
    shown = f", the first {hops}:" if len(path) > hops else ":"
    print(f"critical path, {len(path)} hops{shown}", file=file)
    for hop in path[:hops]:
        print(f"  +{hop['offset_s'] * 1000:>9.1f} ms  {hop['name']:<24} "
              f"{hop['duration_s'] * 1000:>9.1f} ms", file=file)


def select_interval(spans, since, until, between):
    """Print the report of an interval; its records, or None with a reason."""
    if between:
        marks = [s for s in spans if s["name"] == between and s.get("start_mono") is not None]
        if not marks:
            print(f"trace_report: no record {between!r} with a monotonic start", file=sys.stderr)
            return None
        since = min(s["start_mono"] for s in marks)
        until = max(s["start_mono"] + s["duration_s"] for s in marks)
    selected = flight.between(spans, since, until)
    print_interval(f"[{since}, {until})", flight.interval_report(selected))
    return selected


def select_trace(doc, spans, trace_id):
    """Print the report of one trace; its records, or None with a reason."""
    trace_id = trace_id or default_trace(doc, spans)
    if trace_id is None:
        print("trace_report: no trace ids recorded on any span", file=sys.stderr)
        return None
    selected = [s for s in spans if s.get("trace_id") == trace_id]
    if not selected:
        print(f"trace_report: no spans carry trace id {trace_id!r} "
              f"(try --list)", file=sys.stderr)
        return None
    print_report(trace_id, selected)
    return selected


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("artifact", help="soak-*.json / metrics.json / span array")
    ap.add_argument("--trace", help="trace id to render (default: last round)")
    ap.add_argument("--list", action="store_true",
                    help="list the traces present and exit")
    ap.add_argument("--out", help="write Chrome trace-event JSON here")
    ap.add_argument("--since", type=float, help="report what started from this perf_counter() on")
    ap.add_argument("--until", type=float, help="... and before this one")
    ap.add_argument("--between", metavar="SPAN",
                    help="the interval from the first record of this name to the last one's end")
    args = ap.parse_args()

    try:
        doc = json.loads(open(args.artifact).read())
    except (OSError, ValueError) as e:
        print(f"trace_report: cannot read {args.artifact}: {e}", file=sys.stderr)
        return 1
    spans = extract_spans(doc)
    if not spans:
        print(f"trace_report: no span records in {args.artifact}", file=sys.stderr)
        return 1

    if args.list:
        print(f"{'spans':>6} {'wall_ms':>9}  trace")
        for t in flight.traces_in(spans):
            print(f"{t['spans']:>6} {t['wall_s'] * 1000:>9.1f}  {t['trace_id']}")
        return 0

    if args.between or args.since is not None or args.until is not None:
        selected = select_interval(spans, args.since, args.until, args.between)
    else:
        selected = select_trace(doc, spans, args.trace)
    if selected is None:
        return 1

    if args.out:
        with open(args.out, "w") as fh:
            fh.write(flight.chrome_trace_json(selected))
        print(f"\nchrome trace written to {args.out} "
              f"(open in chrome://tracing or ui.perfetto.dev)")
    return 0


if __name__ == "__main__":
    try:
        raise SystemExit(main())
    except BrokenPipeError:  # e.g. piped into head
        raise SystemExit(0)
