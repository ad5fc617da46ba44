"""Host seconds a round the feed waited on **the link's own bound**: the sum of
the program's ``fabric.feed.wait`` spans with ``on == "link"`` (the oldest
crossing chunk's landing, before a put that would make more than
``round.LINK_BYTES`` cross at once) inside a round.
Read from the program's span ring (reached by dotted path), cut by the harness's
own ``round`` spans: both clocks are ``time.perf_counter()``, so the number
needs no profiler, and in a traced run it is what the profiler's link left of
the round. Median over the window's rounds; nothing where no round holds a
``fabric.feed`` record with a monotonic start."""

import statistics

from benchmark import traffic

name = "feed.link_wait_s"
unit = "s"
layer = "host feed"
moves = "round_s"
reads_spans = ("round",)

RING = "sda_tpu.telemetry.snapshot"


def _seconds(mine) -> float:
    """Of the ``fabric.feed*`` records of one round."""
    return sum(
        r["duration_s"] for r in mine
        if r["name"] == "fabric.feed.wait" and r["attrs"]["on"] == "link"
    )


def reduce(spans, trace, cell):
    ring = [
        r for r in traffic.resolve(RING)(1 << 20).get("spans", ())
        if r["name"].startswith("fabric.feed") and r.get("start_mono") is not None
    ]
    per_round = []
    for round_span in (s for s in spans if s.name == "round"):
        mine = [r for r in ring if round_span.start <= r["start_mono"] < round_span.end]
        if any(r["name"] == "fabric.feed" for r in mine):
            per_round.append(_seconds(mine))
    return statistics.median(per_round) if per_round else None
