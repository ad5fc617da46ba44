"""Round flight recorder: Chrome trace export validity/determinism and
the round_report waterfall/overlap/critical-path math on hand-built spans."""

import json

import pytest

from sda_tpu.telemetry import flight


def _span(name, start, dur, trace_id="t1", **attrs):
    return {
        "name": name,
        "trace_id": trace_id,
        "start": start,
        "duration_s": dur,
        "attrs": attrs or None,
    }


# A hand-built pipelined round (seconds, offsets from 100.0):
#   ingest.upload   [0.0, 1.0)
#   clerk.download  [0.5, 1.5)   -- overlaps the upload tail
#   clerk.decrypt   [1.5, 2.0)
#   reveal.fold     [2.5, 3.0)   -- after a 0.5s gap
ROUND = [
    _span("ingest.upload", 100.0, 1.0, rows=8),
    _span("clerk.download", 100.5, 1.0),
    _span("clerk.decrypt", 101.5, 0.5),
    _span("reveal.fold", 102.5, 0.5),
]


# -- chrome trace export -----------------------------------------------------


def test_chrome_trace_is_valid_and_deterministic():
    doc = flight.chrome_trace(ROUND)
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    # metadata rows name the process and each used track
    meta = [e for e in events if e["ph"] == "M"]
    assert {"process_name", "thread_name", "thread_sort_index"} <= {
        e["name"] for e in meta
    }
    named_tracks = {
        e["args"]["name"] for e in meta if e["name"] == "thread_name"
    }
    assert named_tracks == {"ingest", "clerk", "reveal"}
    # one X event per span, µs timestamps relative to the earliest start
    xs = [e for e in events if e["ph"] == "X"]
    assert len(xs) == len(ROUND)
    by_name = {e["name"]: e for e in xs}
    assert by_name["ingest.upload"]["ts"] == 0.0
    assert by_name["ingest.upload"]["dur"] == pytest.approx(1e6)
    assert by_name["reveal.fold"]["ts"] == pytest.approx(2.5e6)
    assert by_name["clerk.download"]["cat"] == "clerk"
    assert by_name["ingest.upload"]["args"]["rows"] == 8
    assert by_name["ingest.upload"]["args"]["trace_id"] == "t1"
    # distinct tracks per stage
    assert by_name["ingest.upload"]["tid"] != by_name["clerk.decrypt"]["tid"]

    # byte-identical across calls and round-trippable (Perfetto-loadable)
    j1 = flight.chrome_trace_json(ROUND)
    j2 = flight.chrome_trace_json(list(reversed(ROUND)))  # order-insensitive
    assert j1 == j2
    assert json.loads(j1) == doc


def test_chrome_trace_skips_unfinished_spans():
    spans = ROUND + [_span("clerk.download", 103.0, None)]
    xs = [e for e in flight.chrome_trace(spans)["traceEvents"] if e["ph"] == "X"]
    assert len(xs) == len(ROUND)


def test_chrome_trace_empty():
    doc = flight.chrome_trace([])
    assert [e["ph"] for e in doc["traceEvents"]] == ["M"]  # process_name only


# -- round_report ------------------------------------------------------------


def test_round_report_numbers():
    r = flight.round_report(ROUND)
    assert r["spans"] == 4
    assert r["wall_s"] == pytest.approx(3.0)
    # union coverage: [0,2.0) plus [2.5,3.0) -> 2.5s busy, 0.5s gap
    assert r["busy_s"] == pytest.approx(2.5)
    assert r["span_s"] == pytest.approx(3.0)
    assert r["overlap_efficiency"] == pytest.approx((3.0 - 2.5) / 3.0, abs=1e-4)

    rows = {row["stage"]: row for row in r["stages"]}
    assert list(rows) == ["ingest", "clerk", "reveal"]  # ordered by first start
    assert rows["ingest"]["offset_s"] == 0.0
    assert rows["clerk"]["offset_s"] == pytest.approx(0.5)
    assert rows["clerk"]["busy_s"] == pytest.approx(1.5)
    assert rows["clerk"]["spans"] == 2
    assert rows["reveal"]["share"] == pytest.approx(0.5 / 3.0, abs=1e-3)

    # critical path: upload holds the clock first, the download reaches
    # past its end, then decrypt, gap-jump, then fold
    names = [h["name"] for h in r["critical_path"]]
    assert names == ["ingest.upload", "clerk.download", "clerk.decrypt",
                     "reveal.fold"]
    assert r["critical_path"][0]["offset_s"] == 0.0
    assert r["critical_path"][1]["offset_s"] == pytest.approx(0.5)


def test_round_report_fully_sequential_and_empty():
    seq = [_span("a.x", 0.0, 1.0), _span("b.y", 1.0, 1.0)]
    r = flight.round_report(seq)
    assert r["overlap_efficiency"] == 0.0
    assert [h["name"] for h in r["critical_path"]] == ["a.x", "b.y"]

    empty = flight.round_report([])
    assert empty["spans"] == 0 and empty["stages"] == []
    assert empty["critical_path"] == []


def test_critical_path_containment():
    # a short span fully inside a long one never appears on the path
    spans = [_span("svc.outer", 0.0, 5.0), _span("svc.inner", 1.0, 1.0)]
    assert [s["name"] for s in flight.critical_path(spans)] == ["svc.outer"]


def test_traces_in_groups_and_orders():
    spans = (
        [_span("a.x", 10.0, 1.0, trace_id="r1")]
        + [_span("b.y", 11.0, 2.0, trace_id="r2")]
        + [_span("a.z", 10.5, 1.0, trace_id="r1")]
        + [_span("c.w", 12.0, 1.0, trace_id=None)]  # untraced: dropped
    )
    out = flight.traces_in(spans)
    assert [t["trace_id"] for t in out] == ["r1", "r2"]
    assert out[0]["spans"] == 2
    assert out[0]["wall_s"] == pytest.approx(1.5)


# -- one clock a list ---------------------------------------------------------


def _mono(name, start_mono, dur, wall_offset=1_000_000.0, **attrs):
    """A record of the ring as it is now: both starts, the duration on the
    monotonic one. The wall clock is made to disagree with the monotonic one
    about the order, so that a reader on the wrong clock is caught."""
    return {
        "name": name,
        "trace_id": None,
        "start": wall_offset - start_mono,
        "attrs": attrs or None,
        "start_mono": start_mono,
        "duration_s": dur,
    }


def test_records_with_a_monotonic_start_are_read_on_it():
    spans = [_mono("a.x", 10.0, 1.0), _mono("b.y", 11.0, 2.0)]
    assert [s["name"] for s in flight.critical_path(spans)] == ["a.x", "b.y"]
    r = flight.round_report(spans)
    assert r["wall_s"] == pytest.approx(3.0) and r["busy_s"] == pytest.approx(3.0)
    xs = {e["name"]: e for e in flight.chrome_trace(spans)["traceEvents"] if e["ph"] == "X"}
    assert xs["a.x"]["ts"] == 0.0 and xs["b.y"]["ts"] == pytest.approx(1e6)


def test_mixed_records_order_and_export_deterministically():
    """A banked record has no ``start_mono``: a list that holds one is read on
    the wall clock throughout, the one clock every record of it has."""
    old = _span("clerk.decrypt", 101.5, 0.5)
    new = dict(_mono("ingest.upload", 7.0, 1.0), start=100.0)
    newer = dict(_mono("reveal.fold", 9.5, 0.5), start=102.5)
    mixed = [newer, old, new]
    assert [s["name"] for s in flight.critical_path(mixed)] == [
        "ingest.upload", "clerk.decrypt", "reveal.fold",
    ]
    assert flight.round_report(mixed)["wall_s"] == pytest.approx(3.0)
    assert flight.chrome_trace_json(mixed) == flight.chrome_trace_json(list(reversed(mixed)))
    xs = {e["name"]: e for e in flight.chrome_trace(mixed)["traceEvents"] if e["ph"] == "X"}
    assert xs["clerk.decrypt"]["ts"] == pytest.approx(1.5e6)
    # and a cut by the monotonic clock leaves the banked record out
    assert [s["name"] for s in flight.between(mixed, 0.0, 8.0)] == ["ingest.upload"]
    assert flight.between(mixed) == mixed


def test_the_fabric_and_jax_spans_have_tracks_of_their_own():
    spans = [
        _mono("fabric.feed", 0.0, 1.0), _mono("fabric.feed.put", 0.1, 0.1),
        _mono("fabric.step.dispatch", 0.2, 0.1, step=0),
        _mono("fabric.epilogue.recombine", 1.0, 0.1), _mono("fabric.reconstruct", 1.1, 0.1),
        _mono("fabric.unmask.combine", 1.2, 0.1), _mono("jax.lower", 1.3, 0.1, program="jit_step"),
        _mono("fabric.something_else", 1.4, 0.1),
    ]
    events = flight.chrome_trace(spans)["traceEvents"]
    tracks = {e["tid"]: e["args"]["name"] for e in events if e["name"] == "thread_name"}
    by_name = {e["name"]: tracks[e["tid"]] for e in events if e["ph"] == "X"}
    assert by_name == {
        "fabric.feed": "fabric.feed", "fabric.feed.put": "fabric.feed",
        "fabric.step.dispatch": "fabric.feed",
        "fabric.epilogue.recombine": "fabric.epilogue", "fabric.reconstruct": "fabric.epilogue",
        "fabric.unmask.combine": "fabric.unmask", "jax.lower": "jax",
        "fabric.something_else": "other",
    }
    lower = next(e for e in events if e["name"] == "jax.lower")
    assert lower["args"]["program"] == "jit_step"


# -- interval_report -----------------------------------------------------------

# Two made-up host-fed rounds, at 100.0 and 110.0. A round: the feed's call
# [0, 1.0) with two puts of 0.05, a wait on the link of 0.3 and one on
# in_flight of 0.2 inside it; then, outside the call, the epilogue.


def _hostfed_round(t, link_wait=0.3):
    return [
        _mono("fabric.feed", t, 1.0, in_flight=3, bytes=200),
        _mono("fabric.feed.put", t + 0.10, 0.05, rows=1, bytes=100),
        _mono("fabric.feed.wait", t + 0.20, link_wait, on="link"),
        _mono("fabric.feed.put", t + 0.60, 0.05, rows=1, bytes=100),
        _mono("fabric.feed.wait", t + 0.70, 0.2, on="in_flight"),
        _mono("fabric.epilogue.share_matmul", t + 1.5, 0.25),
    ]


HOSTFED = [_mono("jax.compile", 50.0, 2.0, program="jit_step")] + _hostfed_round(100.0) + (
    _hostfed_round(110.0, link_wait=0.1)
)


def test_interval_report_of_a_hostfed_round_adds_up():
    r = flight.interval_report(HOSTFED, 100.0, 105.0)
    names = r["names"]
    assert list(names) == [
        "fabric.feed", "fabric.feed.put", "fabric.feed.wait{on=link}",
        "fabric.feed.wait{on=in_flight}", "fabric.epilogue.share_matmul",
    ]
    assert names["fabric.feed.put"] == {
        "count": 2, "seconds": pytest.approx(0.1), "own_s": pytest.approx(0.1),
    }
    assert names["fabric.feed.wait{on=link}"]["seconds"] == pytest.approx(0.3)
    assert names["fabric.feed.wait{on=in_flight}"]["seconds"] == pytest.approx(0.2)
    call = names["fabric.feed"]
    assert call["count"] == 1 and call["seconds"] == pytest.approx(1.0)
    # the call less its puts and its waits is the host's own
    assert call["own_s"] == pytest.approx(1.0 - 0.1 - 0.3 - 0.2)
    inside = sum(names[n]["seconds"] for n in names if n.startswith("fabric.feed."))
    assert call["own_s"] + inside == pytest.approx(call["seconds"])
    # the summary is round_report's own, of the cut
    cut = flight.between(HOSTFED, 100.0, 105.0)
    whole = flight.round_report(cut)
    for key in ("spans", "wall_s", "busy_s", "span_s", "overlap_efficiency", "critical_path"):
        assert r[key] == whole[key], key
    assert r["spans"] == 6 and r["busy_s"] == pytest.approx(1.25)
    assert [h["name"] for h in r["critical_path"]] == [
        "fabric.feed", "fabric.epilogue.share_matmul",
    ]
    assert "rounds" not in r and "a_round" not in r
    # open on a side; nothing inside
    assert flight.interval_report(HOSTFED, None, 60.0)["names"] == {
        "jax.compile": {"count": 1, "seconds": 2.0, "own_s": 2.0},
    }
    empty = flight.interval_report(HOSTFED, 60.0, 70.0)
    assert empty["spans"] == 0 and empty["names"] == {} and empty["critical_path"] == []


def test_interval_report_gives_each_round_and_the_median_round():
    rounds = [(100.0, 103.0), (110.0, 113.0), (120.0, 123.0)]
    r = flight.interval_report(HOSTFED, 100.0, 130.0, rounds=rounds)
    assert r["names"]["fabric.feed"]["count"] == 2
    assert [set(each["names"]) for each in r["rounds"]][2] == set()
    link = [
        each["names"].get("fabric.feed.wait{on=link}", {}).get("seconds") for each in r["rounds"]
    ]
    assert link == [pytest.approx(0.3), pytest.approx(0.1), None]
    own = [each["names"]["fabric.feed"]["own_s"] for each in r["rounds"][:2]]
    assert own == [pytest.approx(0.4), pytest.approx(0.6)]
    # the median round: a round without the row counts as 0
    a_round = r["a_round"]
    assert a_round["fabric.feed.wait{on=link}"]["seconds"] == pytest.approx(0.1)
    assert a_round["fabric.feed"] == {
        "count": 1, "seconds": pytest.approx(1.0), "own_s": pytest.approx(0.4),
    }
    assert a_round["fabric.feed.put"]["count"] == 2
    # the compile that nests nothing and lies in no round is in no round's row
    assert "jax.compile" not in a_round
    assert flight.interval_report(HOSTFED, 100.0, 130.0, rounds=[])["a_round"] == {}


def test_own_seconds_nest_by_time_at_every_depth():
    spans = [
        _mono("a.outer", 0.0, 10.0), _mono("a.mid", 1.0, 4.0), _mono("a.leaf", 2.0, 1.0),
        _mono("a.leaf", 3.5, 1.0), _mono("a.mid", 6.0, 2.0), _mono("b.after", 9.5, 2.0),
    ]
    names = flight.interval_report(spans)["names"]
    assert names["a.outer"]["own_s"] == pytest.approx(10.0 - 4.0 - 2.0)
    assert names["a.mid"]["own_s"] == pytest.approx(6.0 - 2.0)
    assert names["a.leaf"]["own_s"] == pytest.approx(2.0)
    # a span that only overlaps its neighbour nests in nothing
    assert names["b.after"]["own_s"] == pytest.approx(2.0)
