"""The 61-bit chunk step as the chip's compiler makes it, at a small shape,
for a described v5e:2x2 (compile only: nothing runs, no time is taken). What
PR 31 took out of the step cannot come back unseen: a copy or reshape of a
tensor the chunk's size, and a second draw of every share value. And the
31-bit per-participant step (PR 38), compiled the same way: the fused kernel
is in it, XLA's 20-million-row tensors are not, and every operation the
chunk's size carries a ``fabric.*`` scope (the benchmark's
``engine.unscoped_s`` counts what does not).

In the manner of ``tests/benchmark/test_benchmark_compile_rehearsal.py``: the
topology is described inside a module-scoped fixture, which skips where it
cannot be, and the compile cache is off around the compiles.
"""

import os
import re

import pytest

ROWS, DIM = 64, 5120  # a chunk of (C, dim); k = 5, so 1 024 batches a row


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # whatever keeps the TPU's compiler from loading
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compiled_wide_step(one_chip):
    """The compiled text of ``value_limb_sums_chunk`` at a 61-bit plan."""
    import jax.numpy as jnp

    from sda_tpu.ops import find_packed_parameters
    from sda_tpu.ops.jaxcfg import ensure_x64
    from sda_tpu.parallel.engine import make_plan
    from sda_tpu.parallel.sumfirst import value_limb_sums_chunk
    from sda_tpu.protocol import PackedShamirSharing

    ensure_x64()
    p, w2, w3 = find_packed_parameters(5, 2, 8, min_modulus_bits=61, seed=1)
    plan = make_plan(PackedShamirSharing(5, 8, 2, p, w2, w3), DIM)
    return _compile_for(
        one_chip, lambda secrets, key: value_limb_sums_chunk(secrets, key, plan), jnp.int64
    )


def _compile_for(one_chip, entry, dtype):
    """The compiled text of ``entry(secrets, key)`` at (ROWS, DIM)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    key = jax.eval_shape(lambda: jax.random.key(0))
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return (
            jax.jit(entry)
            .lower(
                jax.ShapeDtypeStruct((ROWS, DIM), dtype, sharding=one_chip),
                jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one_chip),
            )
            .compile()
            .as_text()
        )
    finally:
        jax.config.update("jax_enable_compilation_cache", before)


@pytest.fixture(scope="module")
def compiled_narrow_step(one_chip):
    """The compiled text of ``share_combine_limb`` at c4-w31-d50k's plan."""
    import jax.numpy as jnp

    from sda_tpu.ops import find_packed_parameters
    from sda_tpu.ops.jaxcfg import ensure_x64
    from sda_tpu.parallel.engine import make_plan, share_combine_limb
    from sda_tpu.protocol import PackedShamirSharing

    ensure_x64()
    p, w2, w3 = find_packed_parameters(5, 2, 8, min_modulus_bits=30, seed=0)
    plan = make_plan(PackedShamirSharing(5, 8, 2, p, w2, w3), DIM)
    return _compile_for(
        one_chip, lambda secrets, key: share_combine_limb(secrets, key, plan), jnp.int32
    )


def _elements(shape_text):
    n = 1
    for d in re.findall(r"\d+", shape_text):
        n *= int(d)
    return n


def test_no_copy_or_reshape_of_a_chunk_sized_tensor(compiled_wide_step):
    entry = compiled_wide_step[compiled_wide_step.index("ENTRY ") :]
    relaid = [
        line.strip()[:120]
        for line in entry.splitlines()
        for m in [re.search(r"= \w+\[([\d,]*)\]\S* (copy|reshape|transpose)\(", line)]
        if m and _elements(m.group(1)) >= ROWS * DIM
    ]
    assert relaid == []


def test_the_threefry_rounds_are_in_one_fused_computation(compiled_wide_step):
    """A threefry draw is twenty rotations; a fused computation that holds a
    draw holds at least as many left shifts. One holds both words' draws."""
    bodies = re.findall(r"\n%?(fused_computation[\w.]*) [^\n]*\{\n(.*?)\n\}", compiled_wide_step, re.S)
    drawing = [name for name, body in bodies if len(re.findall(r" shift-left\(", body)) >= 20]
    assert len(drawing) == 1, drawing


def test_the_narrow_step_is_the_fused_kernel_and_no_row_of_xlas_formulation(compiled_narrow_step):
    entry = compiled_narrow_step[compiled_narrow_step.index("ENTRY ") :]
    kernels = re.findall(r"= \S+ custom-call\([^\n]*custom_call_target=\"tpu_custom_call\"", entry)
    assert len(kernels) == 1 and "limb_share_combine" in entry
    assert " convolution(" not in compiled_narrow_step and " dot(" not in compiled_narrow_step
    # no tensor with the (participants x batches) axis major
    assert not re.search(rf"\[{ROWS * (DIM // 5)},\d+\]", compiled_narrow_step)


def test_every_chunk_sized_operation_of_the_narrow_step_is_scoped(compiled_narrow_step):
    """The transpose of the chunk is the compiler's layout change of a
    parameter, which would carry the parameter's name; the step gives it
    ``fabric.values`` (``engine._values_by_dim``). The draw, laid out for the
    kernel in the fusion that makes it, stays ``fabric.rand``."""
    entry = compiled_narrow_step[compiled_narrow_step.index("ENTRY ") :]
    big = {}
    for line in entry.splitlines():
        m = re.search(r"%?(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(", line)
        # the chunk (ROWS x DIM) and the draw (two fifths of it); not the kernel's constant
        relays = m and m.group(3) in ("copy", "fusion", "transpose", "reshape", "slice", "pad")
        if relays and _elements(m.group(2)) >= ROWS * DIM // 5 * 2:
            name = re.search(r'op_name="([^"]*)"', line)
            big[m.group(1)] = name.group(1) if name else None
    assert len(big) >= 2, big
    unscoped = {k: v for k, v in big.items() if v is None or "/fabric." not in v}
    assert unscoped == {}
    assert any("/fabric.values/" in v for v in big.values())
    assert any("/fabric.rand/" in v for v in big.values())
