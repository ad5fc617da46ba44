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
    """The programs of the cell's round, with the shapes they take, on the
    described devices: the window's step(s), and the input's maker."""
    from benchmark import harness

    devices = list(topo.devices)
    round_module = harness.round_of(cell)
    return round_module.steps(cell, devices), round_module.input_maker(cell, devices)


def _chunk(args):
    """The step's chunk among its example arguments: the one that is sharded
    like the resident input, the largest."""
    return max(args, key=lambda a: a.size)


def _resident_bytes(cell, chunk):
    """Bytes of the whole resident input on one chip."""
    return cell.traffic.steps_per_pass * chunk.size * chunk.dtype.itemsize // cell.chips


@pytest.mark.parametrize("workload", CELLS)
def test_chunk_step_compiles_for_v5e_and_fits(workload, topo, quiet_cache):
    from benchmark import harness

    cell = harness.load_cell(REPO, workload)
    steps, _maker = _described(cell, topo)
    (step, args), = steps  # these cells' round runs one program in the window
    compiled = step.lower(*args).compile()
    memory = compiled.memory_analysis()
    # the resident input (the step's chunk is part of it) + the step's
    # temporaries and output, on one chip, inside that chip's memory
    total = (
        _resident_bytes(cell, _chunk(args)) + memory.temp_size_in_bytes
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
    steps, (maker, maker_args) = _described(cell, topo)
    compiled = maker.lower(*maker_args).compile()
    memory = compiled.memory_analysis()
    total = (
        _resident_bytes(cell, _chunk(steps[0][1])) + memory.argument_size_in_bytes
        + memory.temp_size_in_bytes + memory.output_size_in_bytes
    )
    assert total < HBM_BYTES, (workload, total, memory)
