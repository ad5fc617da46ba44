"""The fabric names its own work: ``fabric.*`` device scopes in the compiled
programs of the entries the benchmark's cells run, ``fabric.*`` host spans
through ``telemetry.span`` (records of the ring, and annotations of a
``jax.profiler`` trace), stable kernel names off the cells' paths. Names and
what reads them: docs/observability.md, "Fabric"."""

import os
import pathlib
import re
import subprocess
import sys

import numpy as np
import pytest

from sda_tpu import telemetry

REPO = pathlib.Path(__file__).resolve().parents[1]
DIM = 33  # not a multiple of k = 5: the batching pads, so its scope holds an operation
ROWS = 8


def plan_for(bits):
    from sda_tpu.ops import find_packed_parameters
    from sda_tpu.parallel.engine import make_plan
    from sda_tpu.protocol import PackedShamirSharing

    p, w2, w3 = find_packed_parameters(5, 2, 8, min_modulus_bits=bits, seed=1)
    scheme = PackedShamirSharing(5, 8, 2, p, w2, w3)
    return scheme, make_plan(scheme, DIM)


def secrets_for(plan, seed=0):
    import jax.numpy as jnp

    from sda_tpu.ops.jaxcfg import ensure_x64

    ensure_x64()
    rng = np.random.default_rng(seed)
    values = rng.integers(0, plan.modulus, size=(ROWS, DIM), dtype=np.int64)
    return jnp.asarray(values, dtype=jnp.int64 if plan.modulus > 1 << 31 else jnp.int32)


def chunk_entry(plan):
    from sda_tpu.parallel import sumfirst

    return lambda secrets, key: sumfirst.value_limb_sums_chunk(secrets, key, plan)


def sharded_entry(plan):
    import jax
    from jax.sharding import Mesh

    from sda_tpu.parallel import sumfirst

    mesh = Mesh(np.array(jax.devices("cpu")[:4]).reshape(4, 1), ("p", "d"))
    return sumfirst.sharded_value_limb_sums(plan, mesh)


def participant_entry(plan):
    from sda_tpu.parallel.engine import share_combine_limb

    return lambda secrets, key: share_combine_limb(secrets, key, plan)


SUMFIRST_SCOPES = {
    "fabric.input/batch", "fabric.input/limb_sum", "fabric.rand/draw", "fabric.rand/limb_sum",
}
ENTRIES = {
    "value_limb_sums_chunk": (chunk_entry, SUMFIRST_SCOPES),
    "sharded_value_limb_sums": (sharded_entry, SUMFIRST_SCOPES | {"fabric.psum"}),
    "share_combine_limb": (participant_entry, {
        "fabric.input/batch", "fabric.rand/draw", "fabric.values",
        "fabric.share_matmul/limbs", "fabric.share_matmul/dot", "fabric.combine",
    }),
}


def compiled_text(entry, plan):
    import jax

    return jax.jit(entry(plan)).lower(secrets_for(plan), jax.random.key(0)).compile().as_text()


def op_names(text):
    return re.findall(r'op_name="([^"]*)"', text)


@pytest.mark.parametrize("bits", [61, 31])
@pytest.mark.parametrize("entry", sorted(ENTRIES))
def test_the_compiled_program_holds_every_scope_of_its_entry(entry, bits):
    build, scopes = ENTRIES[entry]
    names = op_names(compiled_text(build, plan_for(bits)[1]))
    for scope in scopes:
        assert any(f"/{scope}/" in f"{name}/" for name in names), (entry, bits, scope)


@pytest.mark.parametrize("bits", [61, 31])
def test_every_fusion_of_the_sumfirst_chunk_is_input_or_randomness(bits):
    text = compiled_text(chunk_entry, plan_for(bits)[1])
    entry = text[text.index("ENTRY "):]
    fusions = re.findall(r'= [^\n]*? fusion\([^\n]*?op_name="([^"]*)"', entry)
    assert len(fusions) >= 4
    # all but the join of the two tiny results, which belongs to neither side
    # (the CPU's compiler folds the reductions into it; the chip's does not)
    apart = [n for n in fusions if "/fabric.input/" not in n and "/fabric.rand/" not in n]
    assert all(n.endswith("/concatenate") for n in apart) and len(apart) <= 1, apart


def round_trip(bits, participant):
    """One tiny round through a device entry and its host epilogue."""
    import jax

    from sda_tpu.ops.shamir import reconstruct_clerk_sums_host
    from sda_tpu.parallel import limbmatmul, sumfirst

    scheme, plan = plan_for(bits)
    secrets, key = secrets_for(plan), jax.random.key(7)
    if participant:
        acc = np.asarray(participant_entry(plan)(secrets, key))
        clerk_sums = limbmatmul.limb_recombine_host(acc, plan.modulus).T
    else:
        acc = np.asarray(chunk_entry(plan)(secrets, key))
        clerk_sums = sumfirst.clerk_sums_from_limb_acc(acc, plan)[0]
    aggregate = reconstruct_clerk_sums_host(clerk_sums, range(7), scheme, DIM)
    want = np.asarray(secrets, dtype=object).sum(axis=0) % plan.modulus
    assert np.array_equal(np.mod(aggregate, plan.modulus), want.astype(np.int64))
    return acc, clerk_sums, aggregate


@pytest.fixture
def fresh_telemetry():
    telemetry.reset()
    yield
    telemetry.set_enabled(True)
    telemetry.reset()


@pytest.mark.parametrize("bits,participant", [(61, False), (31, False), (31, True), (61, True)])
def test_results_are_bit_identical_with_telemetry_on_and_off(bits, participant, fresh_telemetry):
    on = round_trip(bits, participant)
    assert telemetry.spans(name="fabric.")
    telemetry.reset()
    telemetry.set_enabled(False)
    off = round_trip(bits, participant)
    assert telemetry.spans(name="fabric.") == []
    for a, b in zip(on, off):
        assert a.dtype == b.dtype and np.array_equal(a, b)


def test_host_spans_are_records_with_the_modulus_width_and_the_inputs_shape(fresh_telemetry):
    with telemetry.span("caller.round") as caller:
        acc, clerk_sums, _ = round_trip(61, participant=False)
    modulus = plan_for(61)[1].modulus
    spans = {s["name"]: s for s in telemetry.spans(name="fabric.")}
    # the ring holds spans as they end: in the order they ran, before the caller's
    assert list(spans) == [
        "fabric.epilogue.recombine", "fabric.epilogue.share_matmul", "fabric.reconstruct",
    ]
    assert telemetry.spans()[-1] is caller
    assert sum(s["duration_s"] for s in spans.values()) <= caller["duration_s"]
    for span in spans.values():
        assert span["duration_s"] > 0 and span["start"] >= caller["start"]
        assert span["attrs"]["modulus_bits"] == modulus.bit_length()
    # seconds per element is a span's duration over the product of its shape
    assert spans["fabric.epilogue.recombine"]["attrs"]["shape"] == acc.shape
    assert spans["fabric.epilogue.share_matmul"]["attrs"]["shape"] == acc.shape[1:]
    assert spans["fabric.reconstruct"]["attrs"]["shape"] == (7, clerk_sums.shape[1])
    # the road each took: at 61 bits all three are mod_limbs_np's, no python integer
    assert {s["attrs"]["path"] for s in spans.values()} == {"limb"}
    assert set(wide_products()) == {"limb"}


def wide_products():
    """Host mod-p products at wide moduli since the last reset, by path."""
    return {
        dict(labels)["path"]: value
        for (name, labels), value in telemetry.get_registry().snapshot()["counters"].items()
        if name == "sda_wide_mod_products_total"
    }


@pytest.mark.parametrize("bits,participant,path", [
    (30, False, "int64"),  # a 31-bit prime, as c4-w31-d50k's: one limb, the narrow matmul
    (30, True, "int64"),
    (31, False, "limb"),  # a 32-bit prime: two limbs already
    (31, True, "limb"),
    (61, True, "limb"),
])
def test_every_host_span_says_the_path_it_took(bits, participant, path, fresh_telemetry):
    round_trip(bits, participant)
    spans = telemetry.spans(name="fabric.")
    assert [s["attrs"]["path"] for s in spans] == [path] * (2 if participant else 3)
    assert set(spans[0]["attrs"]) == {"modulus_bits", "shape", "path"}
    # the plan's share matrix is built by wide products too: none by python integers
    assert set(wide_products()) == ({"limb"} if path == "limb" else set())


def test_an_accumulator_the_limb_road_refuses_is_reduced_as_python_integers_and_says_so(
    fresh_telemetry,
):
    import jax

    from sda_tpu.parallel import sumfirst

    plan = plan_for(61)[1]
    acc = np.asarray(chunk_entry(plan)(secrets_for(plan), jax.random.key(7)))
    want = sumfirst.clerk_sums_from_limb_acc(acc, plan)
    telemetry.reset()  # the plan's and the first epilogue's products are counted no more
    # not machine integers: the same sums as python integers
    got = sumfirst.clerk_sums_from_limb_acc(acc.astype(object), plan)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    assert [s["attrs"]["path"] for s in telemetry.spans(name="fabric.")] == ["object", "limb"]
    assert wide_products() == {"object": 1, "limb": 1}


def test_the_participant_engines_recombine_is_the_same_span(fresh_telemetry):
    acc, _, _ = round_trip(61, participant=True)
    spans = telemetry.spans(name="fabric.")
    assert [s["name"] for s in spans] == ["fabric.epilogue.recombine", "fabric.reconstruct"]
    bits = plan_for(61)[1].modulus.bit_length()
    assert spans[0]["attrs"] == {"modulus_bits": bits, "shape": acc.shape, "path": "limb"}


def test_host_spans_are_annotations_of_a_profiler_trace(tmp_path, fresh_telemetry):
    import jax
    from jax.profiler import ProfileData

    round_trip(61, participant=False)  # compiled before the trace starts
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("caller.round"):
            round_trip(61, participant=False)
    finally:
        jax.profiler.stop_trace()
    trace = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    events = {
        e.name: (e.start_ns, e.start_ns + e.duration_ns)
        for plane in ProfileData.from_file(str(trace)).planes
        for line in plane.lines for e in line.events
        if e.name.startswith("fabric.") or e.name == "caller.round"
    }
    assert set(events) == {
        "caller.round", "fabric.epilogue.recombine", "fabric.epilogue.share_matmul",
        "fabric.reconstruct",
    }
    outer = events.pop("caller.round")
    for start, end in events.values():  # one clock: inside the caller's annotation
        assert outer[0] <= start < end <= outer[1]


def in_a_new_process(code, **env):
    done = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **env),
    )
    assert done.returncode == 0, done.stderr[-2000:]
    return done.stdout


def test_the_kill_switch_leaves_no_record_and_no_annotation():
    out = in_a_new_process(
        "import importlib, jax, numpy\n"
        "from sda_tpu import telemetry\n"
        "spans = importlib.import_module('sda_tpu.telemetry.spans')\n"
        "assert callable(spans._profiler_annotation)\n"
        "spans._profiler_annotation = None  # not reached: calling it would raise\n"
        "from sda_tpu.parallel.limbmatmul import limb_recombine_host\n"
        "with telemetry.span('outer') as record:\n"
        "    limb_recombine_host(numpy.ones((2, 3, 4), dtype=numpy.int64), 97)\n"
        "print(record, telemetry.spans())\n",
        SDA_TELEMETRY="0",
    )
    assert out.split() == ["None", "[]"]


def test_importing_telemetry_and_the_host_epilogue_imports_no_jax():
    in_a_new_process(
        "import sys, sda_tpu.telemetry, sda_tpu.ops.shamir\n"
        "with sda_tpu.telemetry.span('x') as record:\n"
        "    pass\n"
        "assert record['duration_s'] >= 0\n"
        "assert 'jax' not in sys.modules, 'a span imported jax'\n"
    )


def test_kernels_off_the_cells_paths_have_stable_names():
    import jax
    import jax.numpy as jnp

    from sda_tpu.ops import chacha_pallas
    from sda_tpu.parallel import limb_pallas, limbmatmul

    def kernel_names(fn, *args):
        return [
            eqn.params["name"]
            for eqn in jax.make_jaxpr(fn)(*args).jaxpr.eqns
            if eqn.primitive.name == "pallas_call"
        ]

    seeds = jnp.zeros((4, 4), jnp.uint32)
    assert kernel_names(lambda s: chacha_pallas._rounds_pallas(s, 3, interpret=True), seeds) == [
        "chacha_rounds"
    ]
    plan = plan_for(31)[1]
    stacks = limbmatmul.fold_const_limbs(plan.share_matrix.T, plan.modulus)
    by_dim, draws = jnp.zeros((7 * 5, ROWS), jnp.int32), jnp.zeros((2, 7, ROWS), jnp.int32)
    assert kernel_names(
        lambda c, r: limb_pallas.participant_limb_sums_pallas(c, r, stacks, interpret=True),
        by_dim, draws,
    ) == ["limb_share_combine"]


# -- the per-participant engine's fused layout (PR 38) ---------------------------


def lowered_for_a_tpu(plan, rows=ROWS):
    """The participant entry's StableHLO as lowered for a TPU, with its
    locations: no chip and no compiler, the kernel is a ``tpu_custom_call``."""
    import jax
    import jax.numpy as jnp

    secrets = jax.ShapeDtypeStruct(
        (rows, plan.dim), jnp.int64 if plan.modulus > 1 << 31 else jnp.int32
    )
    key = jax.eval_shape(lambda: jax.random.key(0))
    traced = jax.jit(participant_entry(plan)).trace(secrets, key)
    return traced.lower(lowering_platforms=("tpu",)).as_text(debug_info=True)


def test_the_fused_step_holds_the_scopes_the_cells_metrics_read():
    """``engine.layout_s``, ``engine.share_matmul_s`` and ``engine.rand_s``
    read ``fabric.values``, ``fabric.share_matmul`` and ``fabric.rand``: the
    step a TPU gets names its transpose, its kernel and its draw so, and the
    draw's re-layout stays the draw's."""
    text = lowered_for_a_tpu(plan_for(30)[1])
    assert "tpu_custom_call" in text and "limb_share_combine" in text
    for scope in (
        "fabric.values/optimization_barrier", "fabric.values/transpose",
        "fabric.share_matmul/dot", "fabric.rand/draw", "fabric.rand/layout", "fabric.combine",
    ):
        assert f"/{scope}" in text, scope
    # the 20-million axis of XLA's formulation is in no operation of it
    assert "fabric.share_matmul/limbs" not in text and "dot_general" not in text


def share_combines():
    """Per-participant share + combine programs traced since the last reset, by path."""
    return {
        dict(labels)["path"]: value
        for (name, labels), value in telemetry.get_registry().snapshot()["counters"].items()
        if name == "sda_fabric_share_combine_total"
    }


@pytest.mark.parametrize("backend,bits,rows,path", [
    ("cpu", 30, ROWS, "xla"),  # what a CPU run counts, whatever the width
    ("cpu", 61, ROWS, "xla"),
    ("tpu", 30, ROWS, "fused"),  # c4-w31-d50k's field on a chip
    ("tpu", 31, ROWS, "xla"),  # a 32-bit prime: too wide for int32 limbs
    ("tpu", 61, ROWS, "xla"),
    ("tpu", 30, 3_900, "xla"),  # 3 900 x 35 x 127^2 >= 2^31: past the int32 accumulation
])
def test_a_trace_counts_one_share_combine_by_the_path_it_takes(
    backend, bits, rows, path, monkeypatch, fresh_telemetry
):
    """The path is chosen from the platform, the field's width and the
    chunk's shape, once a trace, and counted where it is chosen, as the
    process's backend makes it (which is what runs the program)."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    plan = plan_for(bits)[1]
    jax.make_jaxpr(participant_entry(plan))(
        jax.ShapeDtypeStruct((rows, DIM), secrets_for(plan).dtype),
        jax.eval_shape(lambda: jax.random.key(0)),
    )
    assert share_combines() == {path: 1}


def test_the_kernels_explicit_entry_counts_its_own_path(fresh_telemetry):
    import jax

    from sda_tpu.parallel import limb_pallas

    plan = plan_for(30)[1]
    limb_pallas.share_combine_limb_pallas(
        secrets_for(plan), jax.random.key(1), plan, interpret=True
    )
    assert share_combines() == {"interpret": 1}
