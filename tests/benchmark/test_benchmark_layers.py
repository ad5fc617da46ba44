"""The per-layer metrics of the set-up, the mask stage, the recipient's unmask
and the two ChaCha kernels, one case each on a made-up run whose numbers can be
worked out on paper: a two-chip trace with both kernels in both programs, a
scope report of both chips, the round's ``unmask`` spans and the program's
records of set-up. A CPU run reaches none of their device arithmetic."""

import dataclasses
import types

import pytest

import bench_tree
from benchmark import harness, models_chacha, trace_reduce

MS = 1_000_000  # ns
P61 = (1 << 60) + 225
SETUP = ("setup.trace_s", "setup.lower_s", "setup.load_s", "setup.compile_s")


def made_up_run():
    """``(spans, trace, context)``: two rounds on two chips. Chip 0 runs the
    rounds kernel 4 + 4 ms and the compaction 5 + 6 ms, chip 1 less; the
    busier chip masks 0.25 s a round, the other 0.2; the chips' partials meet
    in 2 and 4 ms; a round's ``unmask`` is 1.2 s, then 1.0; set-up traced
    0.75 s (one trace nested in another), lowered 0.5, compiled 1.5, loaded 0.4."""
    trace = trace_reduce.Reduced(
        window=(0, 40 * MS),
        ops={
            0: [("jit_masked_step/chacha_rounds.1", 0, 4 * MS),
                ("jit_masked_step/chacha_compact.1", 4 * MS, 9 * MS),
                ("jit_masked_step/fusion.2", 9 * MS, 10 * MS),
                ("jit__fold_chunk/chacha_rounds.1", 10 * MS, 14 * MS),
                ("jit__fold_chunk/chacha_compact.1", 14 * MS, 20 * MS)],
            1: [("jit_masked_step/chacha_rounds.1", 0, 2 * MS),
                ("jit_masked_step/chacha_compact.1", 2 * MS, 5 * MS)],
        },
        async_ops={}, host_spans=[],
    )

    def chip(mask, met):
        return {
            "by_scope": {"fabric.mask": mask, "fabric.unmask": 0.5},
            "by_path": {"fabric.mask/expand": mask - 0.05, "fabric.unmask/expand": 0.375,
                        "fabric.unmask/sum": 0.123, "fabric.unmask/meet": met},
            "unscoped": {"s": 0.01},
        }

    report = {"busiest_chip": "0", "absent": [], "host_spans_s": {},
              "chips": {"0": chip(0.25, 0.002), "1": chip(0.2, 0.004)}}
    ring = [
        {"name": "jax.trace", "start_mono": 1.0, "duration_s": 0.5},
        {"name": "jax.trace", "start_mono": 1.2, "duration_s": 0.1},
        {"name": "jax.trace", "start_mono": 2.0, "duration_s": 0.25},
        {"name": "jax.lower", "start_mono": 1.5, "duration_s": 0.3},
        {"name": "jax.lower", "start_mono": 2.25, "duration_s": 0.2},
        {"name": "jax.compile", "start_mono": 2.5, "duration_s": 1.5},
        {"name": "jax.cache_load", "start_mono": 4.0, "duration_s": 0.4},
    ]
    spans = [harness.Span("unmask", 1, 11.4, 12.6), harness.Span("unmask", 2, 21.6, 22.6)]
    context = harness.LayerContext(
        name="made-up", chips=2, config={"dim": 100_000},
        traffic=types.SimpleNamespace(rows=10, passes=1), rounds=2, elements_per_round=1,
        chunk_bytes=1, acc_bytes=1, steps_per_round=1,
        plan=types.SimpleNamespace(modulus=P61), peaks={"hbm_bytes_per_s": 819e9},
        memory_peak_bytes=0, log=lambda message: None, scopes=report, host_spans={},
        before_window={name: ring for name in SETUP},
    )
    return spans, trace, context


def least(kernel_bytes):
    """Two rounds of the kernel's least bytes for a chip's 10 seeds (ten rows,
    both sides, over two chips) at 819 GB/s."""
    return 2 * kernel_bytes(10, 100_000, P61) / 819e9


#: each hooked metric's number on the made-up run
EXPECTED = {
    "setup.trace_s": 0.75,
    "setup.lower_s": 0.5,
    "setup.load_s": 0.4,
    "setup.compile_s": 1.5,
    "mask.step_s": 0.25,
    "mask.chip_skew": 0.25 / 0.2,
    "unmask.stage_s": 1.1,
    "unmask.expand_s": 0.375,
    "unmask.meet_s": 0.004,
    "chacha_rounds_roofline": 100 * least(models_chacha.rounds_kernel_bytes) / 0.008,
    "chacha_compact_roofline": 100 * least(models_chacha.compact_kernel_bytes) / 0.011,
}


@pytest.fixture(scope="module")
def layers():
    return harness.load_layers(bench_tree.REPO)


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_a_hooked_metric_reads_its_number_from_a_made_up_run_and_nothing_from_an_empty_one(
    layers, metric
):
    spans, trace, context = made_up_run()
    module = layers[metric]
    value = module.reduce(spans, trace, context)
    assert value == pytest.approx(EXPECTED[metric]) and value > 0
    if metric.endswith("_roofline"):
        assert value <= 100 and module.unit == "%"
    # nothing to read: no trace, no report, no span, no records of set-up
    empty = dataclasses.replace(context, scopes=None, before_window={})
    assert module.reduce([], None, empty) is None
    bare = trace_reduce.Reduced(
        window=(0, MS), ops={0: [("jit_step/fusion.1", 0, MS)]}, async_ops={}, host_spans=[]
    )
    assert module.reduce([], bare, empty) is None


def test_the_mesh_metrics_read_every_chip_and_say_nothing_of_one(layers):
    """The meeting is the chip with most, not the busiest; the skew is none on
    one chip, or where a chip masked nothing."""
    spans, trace, context = made_up_run()
    report = context.scopes
    one = dataclasses.replace(context, scopes={**report, "chips": {"0": report["chips"]["0"]}})
    assert layers["mask.chip_skew"].reduce(spans, trace, one) is None
    assert layers["unmask.meet_s"].reduce(spans, trace, one) == 0.002
    idle = {**report["chips"]["1"], "by_scope": {"fabric.unmask": 0.5}}
    lazy = dataclasses.replace(context, scopes={**report, "chips": {"0": report["chips"]["0"], "1": idle}})
    assert layers["mask.chip_skew"].reduce(spans, trace, lazy) is None


def test_the_kernels_are_counted_per_chip_at_the_keystreams_bytes():
    """The rounds kernel makes its states in registers: a block moves its 64 B
    of keystream, and a seed its 16 B; the compaction reads the keystream once
    and writes a seed's accepted draws and count once."""
    blocks = models_chacha.blocks_per_seed(100_000, P61)
    assert blocks == 13_334 and models_chacha.BLOCK_BYTES == 64
    assert models_chacha.rounds_kernel_bytes(500, 100_000, P61) == 500 * (16 + blocks * 64)
    assert models_chacha.compact_kernel_bytes(500, 100_000, P61) == 500 * (
        blocks * 64 + 100_000 * 8 + 4
    )
    # c5-masked's round: 2.67e8 blocks a chip, 0.021 s at 819 GB/s
    seeds = models_chacha.seeds_expanded_per_round(10_000)
    assert models_chacha.rounds_kernel_bytes(seeds, 100_000, P61) / 819e9 == pytest.approx(
        0.02084, rel=1e-3
    )
    # the mesh cell's chip expands its own rows' seeds: the same as one chip's
    assert models_chacha.seeds_expanded_per_round(40_000) // 4 == seeds


def test_the_set_up_records_are_taken_from_the_programs_ring_before_the_window(layers):
    """A set-up metric's ``before_window`` takes the program's ``jax.*``
    records from the process's start on, and none where the ring overwrote a
    record: set-up's may then be cut."""
    import time

    from sda_tpu import telemetry

    telemetry.reset()
    try:
        since = time.perf_counter()
        telemetry._SPANS.record("jax.lower", since - 1.0, 0.5, program="jit_old")
        telemetry._SPANS.record("jax.lower", since + 0.1, 0.25, program="jit_step")
        telemetry._SPANS.record("jax.compile", since + 0.4, 0.75, program="jit_step")
        with telemetry.span("fabric.feed"):
            pass
        for name in SETUP:
            records = layers[name].before_window(since)
            assert [r["attrs"]["program"] for r in records] == ["jit_step", "jit_step"]
        context = types.SimpleNamespace(before_window={"setup.lower_s": records})
        assert layers["setup.lower_s"].reduce([], None, context) == 0.25
        assert layers["setup.lower_s"].before_window(since + 10) is None
        telemetry.counter("sda_telemetry_spans_dropped_total").inc()
        assert layers["setup.lower_s"].before_window(since) is None
    finally:
        telemetry.reset()
