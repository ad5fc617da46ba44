"""Telemetry plane: registry exactness under threads, Prometheus
exposition grammar, trace-id propagation client -> REST -> store, the
request-id header, the legacy ``utils.metrics`` adapter, and the
disabled-mode no-op guarantee (with a loose overhead guard — the precise
<2% number is measured and banked by bench.py on the real ingest bench).
"""

from __future__ import annotations

import re
import threading
import time

import pytest
import requests

from sda_fixtures import new_client
from sda_tpu import telemetry
from sda_tpu.rest import SdaHttpClient, TokenStore, serve_background
from sda_tpu.server import new_mem_server


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.set_enabled(True)
    telemetry.reset()
    yield
    telemetry.set_enabled(True)
    telemetry.reset()


# -- registry ---------------------------------------------------------------


def test_thread_hammer_counters_and_histograms_merge_exactly():
    """N threads x M ops across thread-local shards (threads die before
    the read, exercising shard retirement) must merge to exact totals."""
    n_threads, n_ops = 8, 5000

    def work():
        c = telemetry.counter("t_hammer_total", "hammer", lane="a")
        h = telemetry.histogram("t_hammer_seconds", "hammer")
        for _ in range(n_ops):
            c.inc()
            h.observe(0.001)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    snap = telemetry.get_registry().snapshot()
    total = n_threads * n_ops
    assert snap["counters"][("t_hammer_total", (("lane", "a"),))] == total
    hist = snap["histograms"][("t_hammer_seconds", ())]
    assert hist["count"] == total
    assert hist["sum"] == pytest.approx(total * 0.001)
    assert sum(hist["counts"]) == total


def test_live_snapshot_while_threads_write():
    """snapshot() taken mid-hammer never crashes or loses later writes."""
    stop = threading.Event()

    def work():
        c = telemetry.counter("t_live_total", "live")
        while not stop.is_set():
            c.inc()

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for _ in range(20):
        telemetry.get_registry().snapshot()
    stop.set()
    for t in threads:
        t.join()
    final = telemetry.get_registry().snapshot()["counters"][("t_live_total", ())]
    assert final > 0
    # after joins the count is stable and exactly re-readable
    assert telemetry.get_registry().snapshot()["counters"][("t_live_total", ())] == final


def test_kind_conflict_is_an_error():
    telemetry.counter("t_kind_total", "x")
    with pytest.raises(ValueError, match="t_kind_total"):
        telemetry.histogram("t_kind_total", "x")


def test_disabled_mode_records_nothing():
    telemetry.set_enabled(False)
    telemetry.counter("t_off_total", "x").inc()
    telemetry.histogram("t_off_seconds", "x").observe(1.0)
    with telemetry.span("t.off") as span_record:
        assert span_record is None
    snap = telemetry.get_registry().snapshot()
    assert ("t_off_total", ()) not in snap["counters"]
    assert ("t_off_seconds", ()) not in snap["histograms"]
    assert telemetry.spans(name="t.off") == []


# -- the span ring: one clock, cut by interval, overwrites counted -----------


def test_a_span_record_holds_its_interval_on_the_monotonic_clock():
    before = time.perf_counter()
    with telemetry.span("t.mono", k=1) as record:
        inside = time.perf_counter()
        assert before <= record["start_mono"] <= inside
    after = time.perf_counter()
    end = record["start_mono"] + record["duration_s"]
    assert inside <= end <= after
    # every field a record had stays, the wall-clock start included
    assert list(record) == ["name", "trace_id", "start", "attrs", "start_mono", "duration_s"]
    assert abs(record["start"] - time.time()) < 60
    assert telemetry.snapshot(include_spans=5)["spans"][-1] is record


def test_since_and_until_cut_the_ring_exactly():
    marks = []
    for i in range(5):
        with telemetry.span("t.cut", i=i) as record:
            pass
        marks.append(record["start_mono"])

    def picked(**cut):
        return [s["attrs"]["i"] for s in telemetry.spans(name="t.cut", **cut)]

    assert picked() == [0, 1, 2, 3, 4]
    # half-open: a record that starts at `since` is in, one at `until` is out
    assert picked(since_mono=marks[1], until_mono=marks[3]) == [1, 2]
    assert picked(since_mono=marks[3]) == [3, 4] and picked(until_mono=marks[3]) == [0, 1, 2]
    assert picked(since_mono=marks[4] + 1e-9) == []
    # consecutive intervals share no record and miss none
    edges = [None, *marks[1::2], None]
    cuts = [picked(since_mono=a, until_mono=b) for a, b in zip(edges, edges[1:])]
    assert sum(cuts, []) == [0, 1, 2, 3, 4]
    other = telemetry.spans(trace_id="nobody", since_mono=marks[0])
    assert other == []


def test_an_overwritten_record_ticks_the_dropped_counter():
    from sda_tpu.telemetry import Registry, SpanLog

    registry = Registry(enabled=True)
    ring = SpanLog(registry, maxlen=3)

    def dropped():
        return registry.snapshot()["counters"].get(("sda_telemetry_spans_dropped_total", ()), 0)

    for i in range(3):
        with ring.span("t.ring", i=i):
            pass
    assert dropped() == 0 and len(ring.recent()) == 3
    for i in range(3, 5):
        with ring.span("t.ring", i=i):
            pass
    ring.record("t.told", time.perf_counter() - 0.5, 0.5)
    assert dropped() == 3
    assert [s["name"] for s in ring.recent()] == ["t.ring", "t.ring", "t.told"]
    told = ring.recent(name="t.told")[0]
    assert told["duration_s"] == 0.5 and abs(told["start"] - (time.time() - 0.5)) < 1
    # the process's own ring is the documented size, and says so
    assert telemetry.RING_RECORDS == 4096
    assert "sda_telemetry_spans_dropped_total" in telemetry.prometheus_text()


def test_disabled_mode_reads_no_clock(monkeypatch):
    telemetry.set_enabled(False)

    def no_clock():
        raise AssertionError("a disabled span read a clock")

    monkeypatch.setattr(time, "perf_counter", no_clock)
    monkeypatch.setattr(time, "time", no_clock)
    with telemetry.span("t.off", k=1) as record:
        assert record is None
    monkeypatch.undo()
    assert telemetry.spans() == []


def test_overhead_guard_counter_hot_path():
    """Loose absolute guard against accidentally heavy instrumentation:
    a counter inc must stay in single-digit microseconds (bench.py owns
    the precise <2% enabled-vs-disabled number on the ingest bench)."""
    c = telemetry.counter("t_cost_total", "cost")
    n = 100_000
    t0 = time.perf_counter()
    for _ in range(n):
        c.inc()
    enabled_s = time.perf_counter() - t0
    assert enabled_s / n < 20e-6, f"counter.inc costs {enabled_s / n * 1e6:.1f}us"

    telemetry.set_enabled(False)
    t0 = time.perf_counter()
    for _ in range(n):
        c.inc()
    disabled_s = time.perf_counter() - t0
    assert disabled_s / n < 5e-6, f"disabled inc costs {disabled_s / n * 1e6:.1f}us"


# -- exposition -------------------------------------------------------------

# label values are quoted strings with backslash escaping, so braces
# inside a value (route templates like "/v1/agents/{id}") are legal
_PROM_LINE = re.compile(
    r"^(?:"
    r"# (?:HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* .*"
    r"|[a-zA-Z_:][a-zA-Z0-9_:]*"
    r'(?:\{(?:[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*",?)*\})?'
    r" (?:[+-]?[0-9.eE+-]+|\+Inf|NaN)"
    r")$"
)


def test_prometheus_text_obeys_the_line_grammar():
    telemetry.counter("t_expo_total", "an expo counter", kind="a").inc(3)
    telemetry.histogram("t_expo_seconds", "an expo histogram").observe(0.5)
    telemetry.gauge("t_expo_gauge", "an expo gauge").set(1.25)
    text = telemetry.prometheus_text()
    for line in text.rstrip("\n").split("\n"):
        assert _PROM_LINE.match(line), f"bad exposition line: {line!r}"
    assert 't_expo_total{kind="a"} 3' in text
    assert "# TYPE t_expo_seconds histogram" in text
    # cumulative buckets end at +Inf == _count
    assert 't_expo_seconds_bucket{le="+Inf"} 1' in text
    assert "t_expo_seconds_count 1" in text


def test_label_escaping_survives_round_trip():
    telemetry.counter("t_esc_total", "x", path='we"ird\\lab\nel').inc()
    text = telemetry.prometheus_text()
    assert 't_esc_total{path="we\\"ird\\\\lab\\nel"} 1' in text


# -- REST integration -------------------------------------------------------


@pytest.fixture()
def http_ctx(tmp_path):
    server = new_mem_server()
    with serve_background(server) as base_url:
        yield server, base_url, tmp_path


def test_client_trace_id_lands_in_server_store_spans(http_ctx):
    """The tentpole round trip: a trace id bound on the client rides the
    X-SDA-Trace header through REST dispatch into the store-layer spans."""
    _, base_url, tmp_path = http_ctx
    service = SdaHttpClient(base_url, TokenStore(tmp_path))
    alice = new_client(tmp_path / "alice", service)
    with telemetry.trace("trace-roundtrip-1") as tid:
        assert tid == "trace-roundtrip-1"
        alice.upload_agent()

    store_spans = telemetry.spans(name="store.", trace_id="trace-roundtrip-1")
    assert store_spans, "no store spans carried the client trace id"
    assert all(s["trace_id"] == "trace-roundtrip-1" for s in store_spans)
    assert any(s["attrs"].get("store") == "mem" for s in store_spans)
    # the HTTP dispatch span carries it too. It is recorded when the
    # handler's span block exits — AFTER the response bytes may already
    # have reached the client — so give the server thread a moment.
    deadline = time.monotonic() + 2.0
    while (
        not telemetry.spans(name="http.request", trace_id="trace-roundtrip-1")
        and time.monotonic() < deadline
    ):
        time.sleep(0.01)
    assert telemetry.spans(name="http.request", trace_id="trace-roundtrip-1")


def test_request_id_and_trace_echo_headers(http_ctx):
    _, base_url, _ = http_ctx
    resp = requests.get(
        f"{base_url}/v1/ping", headers={telemetry.TRACE_HEADER: "hdr-trace-1"}
    )
    assert resp.status_code == 200
    assert re.fullmatch(r"[0-9a-f]{16}", resp.headers.get("X-SDA-Request-Id", ""))
    assert resp.headers.get(telemetry.TRACE_HEADER) == "hdr-trace-1"
    # ids are per-request
    other = requests.get(f"{base_url}/v1/ping")
    assert other.headers["X-SDA-Request-Id"] != resp.headers["X-SDA-Request-Id"]


def test_metrics_route_is_unauthenticated_prometheus(http_ctx):
    _, base_url, _ = http_ctx
    requests.get(f"{base_url}/v1/ping")
    resp = requests.get(f"{base_url}/v1/metrics")
    assert resp.status_code == 200
    assert resp.headers["Content-Type"].startswith("text/plain")
    for line in resp.text.rstrip("\n").split("\n"):
        assert _PROM_LINE.match(line), f"bad exposition line: {line!r}"
    assert "sda_http_requests_total" in resp.text
    assert 'route="/v1/ping"' in resp.text

    snap = requests.get(f"{base_url}/v1/metrics.json").json()
    assert snap["enabled"] is True
    assert any(c["name"] == "sda_http_requests_total" for c in snap["counters"])


def test_route_label_is_a_bounded_template(http_ctx):
    _, base_url, tmp_path = http_ctx
    service = SdaHttpClient(base_url, TokenStore(tmp_path))
    alice = new_client(tmp_path / "alice", service)
    alice.upload_agent()
    service.get_agent(alice.agent, alice.agent.id)
    requests.get(f"{base_url}/v1/never/such/route")
    text = requests.get(f"{base_url}/v1/metrics").text
    assert 'route="/v1/agents/{id}"' in text
    assert str(alice.agent.id) not in text  # raw uuids never become labels
    assert 'route="<unmatched>"' in text


# -- legacy adapter ---------------------------------------------------------


def test_legacy_metrics_adapter_feeds_telemetry():
    from sda_tpu.utils.metrics import get_metrics

    m = get_metrics()
    m.reset()
    m.count("snapshots")
    m.count("clerk.participations", 4)
    with m.phase("snapshot.freeze"):
        time.sleep(0.001)

    rep = m.report()
    assert rep["counters"]["snapshots"] == 1
    assert rep["counters"]["clerk.participations"] == 4
    ph = rep["phases"]["snapshot.freeze"]
    assert ph["count"] == 1 and ph["total_s"] > 0 and ph["max_s"] > 0

    # the same events are visible as first-class telemetry series
    snap = telemetry.get_registry().snapshot()
    assert snap["counters"][("sda_events_total", (("event", "snapshots"),))] == 1
    hkey = ("sda_phase_seconds", (("phase", "snapshot.freeze"),))
    assert snap["histograms"][hkey]["count"] == 1
    # phases also emit spans, so trace ids join legacy timers too
    assert telemetry.spans(name="phase.snapshot.freeze")

    # reset() windows the report without wiping unrelated series
    m.reset()
    assert "snapshots" not in m.report()["counters"]
