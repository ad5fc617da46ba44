"""JAX's own trace / lower / compile / cache-load events in the registry and
the span ring (``sda_tpu/telemetry/jaxevents.py``), registered by
``ops.jaxcfg.ensure_x64``: what a program costs before its first dispatch, by
its name, on the clock every other record is on."""

import os
import subprocess
import sys
import time

import numpy as np
import pytest

from sda_tpu import telemetry
from sda_tpu.ops.jaxcfg import ensure_x64

ensure_x64()

import jax
import jax.numpy as jnp

from sda_tpu.telemetry import jaxevents

ROW = np.arange(8)  # a host array: making it compiles nothing
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EVENTS = ("trace", "lower", "compile", "cache_load")


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.set_enabled(True)
    telemetry.reset()
    yield
    telemetry.set_enabled(True)
    telemetry.reset()


def counted() -> dict:
    """``{event: (count, seconds)}`` of the events that were counted."""
    series = {(c["name"], c["labels"].get("event")): c["value"]
              for c in telemetry.snapshot(0)["counters"] if c["name"].startswith("sda_jax_")}
    return {event: (series[("sda_jax_events_total", event)],
                    series[("sda_jax_event_seconds_total", event)])
            for event in EVENTS if ("sda_jax_events_total", event) in series}


def programs(name: str) -> list:
    return [s["attrs"]["program"] for s in telemetry.spans(name=name)]


def fresh_program(tag: str):
    """A jitted function nobody has called, under a name of its own."""

    def body(x):
        return jnp.cumsum(x * 3 + 1)

    body.__name__ = body.__qualname__ = f"events_{tag}"
    return jax.jit(body)


def test_a_fresh_program_leaves_a_lower_and_a_compile_record_by_its_name():
    double = fresh_program("once")
    before = time.perf_counter()
    double(ROW).block_until_ready()
    after = time.perf_counter()
    assert programs("jax.lower") == ["jit_events_once"]
    assert programs("jax.compile") == ["jit_events_once"]  # the tests run with no compile cache
    assert programs("jax.cache_load") == []
    # the traced function's record is kept only from a millisecond on; it is
    # counted either way, and so are the primitives traced inside it
    assert "jit_events_once" not in programs("jax.trace")  # a traced function's own name
    seen = counted()
    assert set(seen) == {"trace", "lower", "compile"}
    assert seen["lower"][0] == seen["compile"][0] == 1 and seen["trace"][0] >= 1
    assert all(seconds > 0 for _count, seconds in seen.values())
    # each record is an interval of the clock the caller's own marks are on
    for record in telemetry.spans(name="jax."):
        assert record["trace_id"] is None and record["duration_s"] > 0
        assert before <= record["start_mono"]
        assert record["start_mono"] + record["duration_s"] <= after
    lower, compiled = telemetry.spans(name="jax.lower")[0], telemetry.spans(name="jax.compile")[0]
    assert lower["start_mono"] + lower["duration_s"] <= compiled["start_mono"] + 1e-3
    assert lower["duration_s"] == pytest.approx(seen["lower"][1])

    # a second call of the same program calls neither listener
    double(ROW).block_until_ready()
    assert counted() == seen and len(telemetry.spans(name="jax.")) >= 2
    assert programs("jax.lower") == ["jit_events_once"]


def test_a_short_trace_is_counted_and_not_kept_and_a_long_one_is_kept():
    trace = "/jax/core/compile/jaxpr_trace_duration"
    jaxevents._on_duration(trace, jaxevents.TRACE_RECORD_FLOOR_S / 2, fun_name="short")
    jaxevents._on_duration(trace, jaxevents.TRACE_RECORD_FLOOR_S, fun_name="long")
    jaxevents._on_duration("/jax/some/other_duration", 5.0, fun_name="other")
    assert programs("jax.trace") == ["long"]
    assert counted() == {"trace": (2, pytest.approx(1.5 * jaxevents.TRACE_RECORD_FLOOR_S))}


def test_a_cache_hit_is_a_load_and_not_a_compile(tmp_path):
    """With a warm persistent cache the compile event wraps a read: the
    record is a ``jax.cache_load`` of the retrieval's seconds under the
    program's name, and all four counters have moved."""
    from jax.experimental.compilation_cache import compilation_cache

    kept = {name: getattr(jax.config, name) for name in (
        "jax_enable_compilation_cache", "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs", "jax_persistent_cache_min_entry_size_bytes",
    )}
    try:
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        compilation_cache.reset_cache()
        fresh_program("cached")(ROW).block_until_ready()
        cold = telemetry.spans(name="jax.compile")
        assert [s["attrs"] for s in cold] == [{"program": "jit_events_cached", "cache": "miss"}]
        assert programs("jax.cache_load") == [] and list(tmp_path.iterdir())
        fresh_program("cached")(ROW).block_until_ready()  # a new jit: the cache is asked
        assert programs("jax.cache_load") == ["jit_events_cached"]
        assert len(telemetry.spans(name="jax.compile")) == 1, "a hit is no compile"
        seen = counted()
        assert set(seen) == set(EVENTS)
        assert seen["cache_load"] == (1, telemetry.spans(name="jax.cache_load")[0]["duration_s"])
        assert seen["compile"][0] == 1 and seen["lower"][0] == 2
    finally:
        for name, value in kept.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()


def test_a_compile_inside_an_interval_is_found_by_name():
    """What a count of compiles in a window cannot say: which program."""
    fresh_program("before")(ROW).block_until_ready()
    since = time.perf_counter()
    fresh_program("inside")(ROW).block_until_ready()
    until = time.perf_counter()
    fresh_program("after")(ROW).block_until_ready()
    inside = telemetry.spans(name="jax.compile", since_mono=since, until_mono=until)
    assert [s["attrs"]["program"] for s in inside] == ["jit_events_inside"]
    assert len(telemetry.spans(name="jax.compile")) == 3


def test_the_listeners_are_registered_once_a_process():
    from jax._src import monitoring  # the public module has no getters

    def ours():
        return (
            sum(f is jaxevents._on_duration for f in monitoring.get_event_duration_listeners()),
            sum(f is jaxevents._on_event for f in monitoring.get_event_listeners()),
        )

    assert ours() == (1, 1)
    jaxevents.register()
    ensure_x64()
    assert ours() == (1, 1)


def test_with_telemetry_off_nothing_is_counted_or_kept():
    telemetry.set_enabled(False)
    fresh_program("off")(ROW).block_until_ready()
    telemetry.set_enabled(True)
    assert telemetry.spans() == [] and counted() == {}


def test_the_kill_switch_leaves_ring_and_counters_empty_in_a_new_process():
    done = subprocess.run(
        [sys.executable, "-c",
         "import sys, sda_tpu.telemetry as t\n"
         "assert 'jax' not in sys.modules, 'importing telemetry imported jax'\n"
         "from sda_tpu.ops.jaxcfg import ensure_x64; ensure_x64()\n"
         "import jax, jax.numpy as jnp\n"
         "jax.jit(lambda x: x + 1)(jnp.arange(4)).block_until_ready()\n"
         "print(t.enabled(), t.spans(), t.snapshot(0)['counters'])\n"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", SDA_TELEMETRY="0"),
    )
    assert done.returncode == 0, done.stderr[-2000:]
    assert done.stdout.split() == ["False", "[]", "[]"]
