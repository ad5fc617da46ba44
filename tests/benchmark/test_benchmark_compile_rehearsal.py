"""Compile rehearsal: every cell's programs at their real shapes, compiled
for a described v5e:2x2 (one chip, and the p=4 mesh for the four-chip cell).
Nothing runs and no time is taken: what the chip's compiler would refuse, a
program that does not fit the chip's memory among it, is refused here, at no
chip time, in every later PR. A round gives its window's programs as a list
(``steps``) and its input's maker (``input_maker``); what is held of them is
in ``cell_checks.py``, and runs here for every cell of the manifest and for a
cell with a round of its own, two programs in its window, dropped into a
temporary tree.

The topology is described inside a module-scoped fixture, which skips where
it cannot be; all these tests stay in this one file (one process loads the
TPU's library).
"""

import json
import os
import pathlib

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
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


@pytest.mark.parametrize("workload", CELLS)
def test_chunk_step_compiles_for_v5e_and_fits(workload, topo, quiet_cache):
    import cell_checks

    cell_checks.check_programs_compile_and_fit(REPO, workload, topo.devices)


@pytest.mark.parametrize("workload", CELLS)
def test_input_and_reference_compile_for_v5e_and_fit(workload, topo, quiet_cache):
    import cell_checks

    cell_checks.check_input_compiles_and_fits(REPO, workload, topo.devices)


def test_a_round_of_two_programs_compiles_for_v5e_by_the_same_checks(
    tmp_path, topo, quiet_cache
):
    """The toy round runs a second program in its window; both are compiled,
    under names of their own, and the first is the chunk step."""
    import bench_tree
    import cell_checks
    from benchmark import harness

    root = bench_tree.copy_benchmark(tmp_path / "copy")
    name = bench_tree.add_toy_cell(root, "toy-masked", "toy_masked")
    with bench_tree.rounds_importable_from(root):
        cell = harness.load_cell(root, name)
        programs = harness.round_of(cell).steps(cell, list(topo.devices))
        assert [jitted.__name__ for jitted, _args in programs] == ["masked_step", "unmask_fold"]
        cell_checks.check_programs_compile_and_fit(root, name, topo.devices)
        cell_checks.check_input_compiles_and_fits(root, name, topo.devices)


def test_two_programs_under_one_name_are_refused(tmp_path, topo, quiet_cache):
    """The trace tells a round's programs apart by name: a round whose
    second program is called what its chunk step is called is refused."""
    import bench_tree
    import cell_checks

    source = (pathlib.Path(__file__).parent / "toy_round.py").read_text()
    assert source.count("def unmask_fold(") == 1
    root = bench_tree.copy_benchmark(tmp_path / "copy")
    name = bench_tree.add_toy_cell(
        root, "toy-one-name", "toy_one_name",
        source=source.replace("unmask_fold(total, mask):", "masked_step(total, mask):")
        .replace("jax.jit(unmask_fold)", "jax.jit(masked_step)"),
    )
    with bench_tree.rounds_importable_from(root):
        with pytest.raises(AssertionError, match="jit_masked_step"):
            cell_checks.check_programs_compile_and_fit(root, name, topo.devices)
