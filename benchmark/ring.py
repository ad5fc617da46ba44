"""The program's span ring as the set-up metrics read it: JAX's trace, lower,
compile and cache-load records (``jax.trace``, ``jax.lower``, ``jax.compile``,
``jax.cache_load``), which the program keeps by program name from the moment it
puts its listeners on ``jax.monitoring``. Reached by dotted path: this file
imports nothing of the program. The records' clock and the harness's are both
``time.perf_counter()``."""

from __future__ import annotations

from benchmark import traffic

#: the program's snapshot of its registry and its span ring
SNAPSHOT = "sda_tpu.telemetry.snapshot"
#: the records the ring overwrote to take new ones
DROPPED = "sda_telemetry_spans_dropped_total"
#: more records than the ring holds: the snapshot gives them all
WHOLE_RING = 1 << 20


def jax_records(since: float) -> list | None:
    """The ring's ``jax.*`` records that start at ``since`` or later, taken
    now: a layer file's ``before_window``, so that the window's records cannot
    push set-up's out of the ring. ``None`` where it holds none (a program
    without the listeners, or one that had made every program before), or has
    overwritten a record, so that set-up's may not be whole."""
    snap = traffic.resolve(SNAPSHOT)(WHOLE_RING)
    if any(c["name"] == DROPPED and c["value"] for c in snap["counters"]):
        return None
    records = [
        r for r in snap.get("spans", ())
        if r["name"].startswith("jax.") and r.get("start_mono") is not None
        and r["start_mono"] >= since
    ]
    return records or None


def seconds(records: list | None, event: str) -> float | None:
    """The sum of the seconds of the records called ``event``; 0 where set-up
    has none of them, ``None`` where there are no records to read."""
    if records is None:
        return None
    return sum(r["duration_s"] for r in records if r["name"] == event)
