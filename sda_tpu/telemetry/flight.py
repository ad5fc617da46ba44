"""Round flight recorder: per-trace span assembly and export.

The SpanLog ring answers "what spans happened recently"; a soak
investigation needs "what did round 317 *look like*" — which stage was
the critical path, how well did the download/compute pipeline overlap,
where did the wall-clock go. This module stitches the flat span records
sharing one trace id into that picture:

- ``chrome_trace(spans)`` exports Chrome trace-event JSON (load it in
  ``chrome://tracing`` or https://ui.perfetto.dev): one "X" complete
  event per span with microsecond timestamps, grouped into per-stage
  tracks (``ingest``, ``clerk``, ``reveal``, ``rest``, ``store``, ...)
  via thread-name metadata events;
- ``round_report(spans)`` computes the numbers ``scripts/trace_report.py``
  prints: a per-stage waterfall (offset/duration/share of wall clock),
  overlap efficiency (how much span time ran concurrently with other
  spans), and the greedy critical path through the timeline;
- ``interval_report(spans, since_mono, until_mono, rounds=None)`` is the
  same summary of whatever started in an interval of the monotonic clock,
  with no trace id asked for (the fabric's spans and JAX's events carry
  none): seconds, count and *own* seconds (a span's less what nests inside
  it by time) by span name, the whole interval's and, where the caller
  hands in its rounds' intervals, each round's and the median round's.

Input is the plain span-record shape the ring stores —
``{name, trace_id, start (epoch s), attrs, start_mono (perf_counter s),
duration_s}`` — so both the live ring (``telemetry.spans(...)``) and spans
banked inside a ``soak-*.json`` artifact feed it unchanged. **One clock a
list:** where every record has ``start_mono``, order, offsets and ends are
on it, the clock ``duration_s`` was taken on; a list with a record from
before the field existed is read on wall ``start`` throughout (those
artifacts still load; their ends mix two clocks, as they always did).
Export is deterministic for a fixed span list: ties sort on (start, name),
ids are assigned in sorted order, and nothing consults the clock.
"""

from __future__ import annotations

import json
import statistics

from .spans import between

#: span-name prefix -> display track (tid) for the trace viewer; prefixes
#: are matched longest-first so e.g. "clerk.chunk" beats "clerk"
_TRACKS = (
    ("ingest", 1),
    ("client", 2),
    ("clerk", 3),
    ("reveal", 4),
    ("rest", 5),
    ("http", 5),
    ("service", 6),
    ("store", 7),
    ("crypto", 8),
    ("fabric.feed", 10),
    ("fabric.step", 10),  # the feed's step dispatches, beside its puts and waits
    ("fabric.epilogue", 11),
    ("fabric.reconstruct", 11),
    ("fabric.unmask", 12),
    ("jax", 13),
)
_OTHER_TRACK = 9

_TRACK_NAMES = {
    1: "ingest",
    2: "client",
    3: "clerk",
    4: "reveal",
    5: "rest",
    6: "service",
    7: "store",
    8: "crypto",
    9: "other",
    10: "fabric.feed",
    11: "fabric.epilogue",
    12: "fabric.unmask",
    13: "jax",
}


def _track_of(name: str) -> int:
    for prefix, tid in _TRACKS:
        if name == prefix or name.startswith(prefix + "."):
            return tid
    return _OTHER_TRACK


def _stage_of(name: str) -> str:
    """Waterfall grouping key: the first dotted component."""
    return name.split(".", 1)[0]


def _timeline(spans) -> list:
    """``(begin, end, record)`` of the finished spans (a live ring may hold
    records mid-flight), on one clock (module doc), sorted deterministically
    by (begin, name)."""
    done = [s for s in spans if s.get("duration_s") is not None]
    clock = "start_mono" if all(s.get("start_mono") is not None for s in done) else "start"
    out = [(s[clock], s[clock] + s["duration_s"], s) for s in done]
    out.sort(key=lambda row: (row[0], row[2]["name"]))
    return out


# -- Chrome trace-event export ----------------------------------------------


def chrome_trace(spans, pid: int = 1) -> dict:
    """Chrome trace-event JSON for a span list (Perfetto-loadable).

    Timestamps are microseconds relative to the earliest span start, so
    the viewer opens at t=0 regardless of wall-clock epoch.
    """
    timeline = _timeline(spans)
    events = [
        {
            "name": "process_name",
            "ph": "M",
            "pid": pid,
            "tid": 0,
            "args": {"name": "sda-round"},
        }
    ]
    used_tracks = sorted({_track_of(s["name"]) for _b, _e, s in timeline})
    for tid in used_tracks:
        events.append(
            {
                "name": "thread_name",
                "ph": "M",
                "pid": pid,
                "tid": tid,
                "args": {"name": _TRACK_NAMES[tid]},
            }
        )
        events.append(
            {
                "name": "thread_sort_index",
                "ph": "M",
                "pid": pid,
                "tid": tid,
                "args": {"sort_index": tid},
            }
        )
    t0 = timeline[0][0] if timeline else 0.0
    for begin, _end, s in timeline:
        args = {"trace_id": s.get("trace_id")}
        if s.get("attrs"):
            args.update(s["attrs"])
        events.append(
            {
                "name": s["name"],
                "cat": _stage_of(s["name"]),
                "ph": "X",
                "pid": pid,
                "tid": _track_of(s["name"]),
                "ts": round((begin - t0) * 1e6, 1),
                "dur": round(s["duration_s"] * 1e6, 1),
                "args": args,
            }
        )
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def chrome_trace_json(spans, pid: int = 1) -> str:
    return json.dumps(chrome_trace(spans, pid=pid), indent=1, sort_keys=True)


# -- interval math -----------------------------------------------------------


def _union_coverage(intervals) -> float:
    """Total length covered by a union of (start, end) intervals."""
    if not intervals:
        return 0.0
    intervals = sorted(intervals)
    covered = 0.0
    cur_s, cur_e = intervals[0]
    for s, e in intervals[1:]:
        if s > cur_e:
            covered += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    return covered + (cur_e - cur_s)


def critical_path(spans) -> list:
    """Greedy walk over the timeline: at each point pick, among spans
    covering it, the one reaching furthest; gaps jump to the next start.

    Returns the chosen span records in order. For a pipelined round this
    reads as "the stage that was holding the wall clock at each moment".
    """
    return [s for _begin, _end, s in _critical_path(_timeline(spans))]


def _critical_path(timeline) -> list:
    if not timeline:
        return []
    path = []
    t = timeline[0][0]
    i = 0
    n = len(timeline)
    while i < n:
        best = None
        j = i
        while j < n and timeline[j][0] <= t + 1e-12:
            if best is None or timeline[j][1] > best[1]:
                best = timeline[j]
            j += 1
        if best is None:
            t = timeline[i][0]  # gap: jump to the next span's start
            continue
        path.append(best)
        t = max(t, best[1])
        while i < n and timeline[i][0] <= t + 1e-12 and timeline[i][1] <= t + 1e-12:
            i += 1
    return path


def _summary(timeline) -> dict:
    """What every report says of a timeline: ``spans``, ``wall_s`` (earliest
    start to latest end), ``busy_s`` (union coverage), ``span_s`` (sum of
    durations), ``overlap_efficiency`` and the ``critical_path`` hops."""
    if not timeline:
        return {
            "spans": 0,
            "wall_s": 0.0,
            "busy_s": 0.0,
            "span_s": 0.0,
            "overlap_efficiency": 0.0,
            "critical_path": [],
        }
    t0 = timeline[0][0]
    span_sum = sum(s["duration_s"] for _b, _e, s in timeline)
    busy = _union_coverage([(b, e) for b, e, _s in timeline])
    return {
        "spans": len(timeline),
        "wall_s": round(max(e for _b, e, _s in timeline) - t0, 6),
        "busy_s": round(busy, 6),
        "span_s": round(span_sum, 6),
        "overlap_efficiency": round((span_sum - busy) / span_sum, 4) if span_sum > 0 else 0.0,
        "critical_path": [
            {
                "name": s["name"],
                "offset_s": round(b - t0, 6),
                "duration_s": round(s["duration_s"], 6),
            }
            for b, _e, s in _critical_path(timeline)
        ],
    }


# -- round report ------------------------------------------------------------


def round_report(spans) -> dict:
    """The numbers behind ``scripts/trace_report.py``:

    - ``wall_s`` — earliest start to latest end;
    - ``busy_s`` — union coverage (time with >=1 span running);
    - ``span_s`` — sum of all span durations;
    - ``overlap_efficiency`` — (span_s - busy_s) / span_s: 0 means fully
      sequential, ->1 means heavily pipelined;
    - ``stages`` — per-stage waterfall rows, ordered by first start:
      {stage, spans, offset_s, busy_s, span_s, share} where share is
      busy_s / wall_s;
    - ``tier_close`` — one row per ``tier.close`` span: the level's
      dispatch mode/width and the per-level ``overlap_efficiency`` the
      fanned-out driver stamped on the span (client/tiers.py);
    - ``critical_path`` — {name, offset_s, duration_s} hops.
    """
    timeline = _timeline(spans)
    report = _summary(timeline)
    wall = report["wall_s"]
    t0 = timeline[0][0] if timeline else 0.0

    stages: dict = {}
    for begin, end, s in timeline:
        stages.setdefault(_stage_of(s["name"]), []).append((begin, end, s))
    report["stages"] = []
    for stage, group in stages.items():  # insertion order: by first start
        busy = _union_coverage([(b, e) for b, e, _s in group])
        report["stages"].append(
            {
                "stage": stage,
                "spans": len(group),
                "offset_s": round(group[0][0] - t0, 6),
                "busy_s": round(busy, 6),
                "span_s": round(sum(s["duration_s"] for _b, _e, s in group), 6),
                "share": round(busy / wall, 4) if wall > 0 else 0.0,
            }
        )
    report["tier_close"] = []
    for _b, _e, s in timeline:
        if s["name"] == "tier.close":
            attrs = s.get("attrs") or {}
            report["tier_close"].append(
                {
                    "tier": attrs.get("tier"),
                    "mode": attrs.get("mode"),
                    "width": attrs.get("width"),
                    "nodes": attrs.get("nodes"),
                    "overlap_efficiency": attrs.get("overlap_efficiency"),
                    "duration_s": round(s["duration_s"], 6),
                }
            )
    return report


# -- interval report ---------------------------------------------------------


def _name_key(span: dict) -> str:
    """The row a span is counted in: its name, a ``fabric.feed.wait`` by the
    bound it waited on (``fabric.feed.wait{on=link}``)."""
    on = (span.get("attrs") or {}).get("on")
    return span["name"] if on is None else f"{span['name']}{{on={on}}}"


def _own_seconds(timeline) -> list:
    """Beside each row of ``timeline``, its span's own seconds: its duration
    less the union of the spans that nest directly inside it **by time**
    (begin and end inside its own, whatever thread they ran on). One sweep
    with the stack of the spans still open; a span that only overlaps the
    open one closes it."""
    children = [[] for _ in timeline]
    order = sorted(range(len(timeline)), key=lambda i: (timeline[i][0], -timeline[i][1]))
    open_ = []
    for i in order:
        begin, end, _s = timeline[i]
        while open_ and timeline[open_[-1]][1] < end:
            open_.pop()
        if open_:
            children[open_[-1]].append((begin, end))
        open_.append(i)
    return [
        (end - begin) - _union_coverage(children[i])
        for i, (begin, end, _s) in enumerate(timeline)
    ]


def _by_name(timeline, own) -> dict:
    """``{row: {count, seconds, own_s}}``, rows in order of first start."""
    rows: dict = {}
    for (_b, _e, s), own_s in zip(timeline, own):
        row = rows.setdefault(_name_key(s), {"count": 0, "seconds": 0.0, "own_s": 0.0})
        row["count"] += 1
        row["seconds"] += s["duration_s"]
        row["own_s"] += own_s
    return rows


def interval_report(spans, since_mono=None, until_mono=None, rounds=None) -> dict:
    """What started in ``[since_mono, until_mono)`` of the monotonic clock
    (``spans.between``; ``None`` leaves that side open), whatever its trace id:

    - ``spans``, ``wall_s``, ``busy_s``, ``span_s``, ``overlap_efficiency``,
      ``critical_path`` — as :func:`round_report` (the same code);
    - ``names`` — ``{row: {count, seconds, own_s}}`` by span name, a wait by
      its bound (``fabric.feed.wait{on=link}``), in order of first start.
      ``own_s`` is the spans' seconds less what nests inside them by time:
      ``fabric.feed``'s is the host's own share of the feed's call, beside
      the puts and the waits it made;
    - with ``rounds``, a list of ``(since_mono, until_mono)`` inside the
      interval: ``rounds`` — each round's ``names``; ``a_round`` — by row, the
      median over the rounds of count, seconds and own seconds (a round
      without the row counts as 0).
    """
    timeline = _timeline(between(spans, since_mono, until_mono))
    own = _own_seconds(timeline)
    report = {"since_mono": since_mono, "until_mono": until_mono, **_summary(timeline)}
    report["names"] = _by_name(timeline, own)
    if rounds is not None:
        each = []
        for lo, hi in rounds:
            inside = [i for i, (b, _e, _s) in enumerate(timeline) if lo <= b < hi]
            names = _by_name([timeline[i] for i in inside], [own[i] for i in inside])
            each.append({"since_mono": lo, "until_mono": hi, "names": names})
        report["rounds"] = each
        nothing = {"count": 0, "seconds": 0.0, "own_s": 0.0}
        report["a_round"] = {
            row: {
                key: statistics.median(r["names"].get(row, nothing)[key] for r in each)
                for key in nothing
            }
            for row in report["names"]
            if any(row in r["names"] for r in each)
        }
    return report


def traces_in(spans) -> list:
    """Distinct trace ids in a span list, ordered by first appearance,
    with span counts: [{trace_id, spans, wall_s}]."""
    seen: dict = {}
    for begin, end, s in _timeline(spans):
        if s.get("trace_id") is not None:
            seen.setdefault(s["trace_id"], []).append((begin, end))
    return [
        {
            "trace_id": tid,
            "spans": len(group),
            "wall_s": round(max(e for _b, e in group) - min(b for b, _e in group), 6),
        }
        for tid, group in seen.items()
    ]
