"""Compile rehearsal: the four cells' programs at their real shapes, compiled
for a described v5e:2x2 (one chip, and the p=4 mesh for the four-chip cell).
Nothing runs and no time is taken: what the chip's compiler would refuse, a
program that does not fit the chip's memory among it, is refused here, at no
chip time, in every later PR.

The topology is described inside a module-scoped fixture, which skips where
it cannot be; all these tests stay in this one file (one process loads the
TPU's library).
"""

import json
import os
import pathlib

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
#: a v5e chip's memory as its runtime reports it (`bytes_limit`, chip run, PR 23)
HBM_BYTES = 16_909_336_064
CELLS = [w["name"] for w in json.loads((REPO / "BENCHMARK.json").read_text())["workloads"]]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # whatever keeps the TPU's compiler from loading
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def quiet_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one: keep the cache off around these."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)


def _described(cell, topo):
    """The cell's program and argument shapes on the described devices."""
    import jax
    from benchmark import harness, traffic

    tr = cell.traffic
    devices = list(topo.devices)[: cell.chips]
    mesh = traffic.make_mesh(tr, devices)
    program = harness.build_program(cell, mesh)
    small = traffic.replicated(devices, mesh)
    key_shape = jax.eval_shape(lambda: jax.random.key(0))
    key = jax.ShapeDtypeStruct(key_shape.shape, key_shape.dtype, sharding=small)
    chunk = jax.ShapeDtypeStruct(
        (tr.chunk, cell.dim),
        traffic.input_dtype(program.modulus),
        sharding=traffic.chunk_sharding(devices, mesh),
    )
    acc = jax.eval_shape(program.chunk_fn, chunk, key_shape)
    acc = jax.ShapeDtypeStruct(acc.shape, acc.dtype, sharding=small)
    index = jax.ShapeDtypeStruct((), "int32", sharding=small)
    halves = jax.ShapeDtypeStruct((2, cell.dim), "int64", sharding=small)
    maker = traffic.chunk_maker(tr, cell.dim, program.modulus, devices, mesh)
    return program, maker, (acc, chunk, key, index), (key, index, halves)


def _resident_bytes(cell, chunk):
    """Bytes of the whole resident input on one chip."""
    return cell.traffic.steps_per_pass * chunk.size * chunk.dtype.itemsize // cell.chips


@pytest.mark.parametrize("workload", CELLS)
def test_chunk_step_compiles_for_v5e_and_fits(workload, topo, quiet_cache):
    from benchmark import harness

    cell = harness.load_cell(REPO, workload)
    program, _maker, args, _maker_args = _described(cell, topo)
    compiled = program.step.lower(*args).compile()
    memory = compiled.memory_analysis()
    # the resident input (the step's chunk is part of it) + the step's
    # temporaries and output, on one chip, inside that chip's memory
    total = (
        _resident_bytes(cell, args[1]) + memory.temp_size_in_bytes
        + memory.output_size_in_bytes
    )
    assert total < HBM_BYTES, (workload, total, memory)
    text = compiled.as_text()
    if cell.chips > 1:
        assert "all-reduce" in text, "the sharded step lost its limb psum"
    else:
        assert "all-reduce" not in text


@pytest.mark.parametrize("workload", CELLS)
def test_input_and_reference_compile_for_v5e_and_fit(workload, topo, quiet_cache):
    from benchmark import harness

    cell = harness.load_cell(REPO, workload)
    _program, maker, args, maker_args = _described(cell, topo)
    compiled = maker.lower(*maker_args).compile()
    memory = compiled.memory_analysis()
    total = (
        _resident_bytes(cell, args[1]) + memory.argument_size_in_bytes
        + memory.temp_size_in_bytes + memory.output_size_in_bytes
    )
    assert total < HBM_BYTES, (workload, total, memory)
