"""The rounds kernel makes its own states (PR 40).

``chacha_rounds`` takes the seeds, the number of blocks a row and a first
block counter; the initial states (constants, key, counter, nonce) are made in
the kernel from a row's key words and an iota, and no ``(P x blocks, 16)``
state tensor exists anywhere in the traced program. Held here, on the
interpreter and at small shapes, to the host's keystream (``ops.chacha``'s
numpy blocks, which share no code with the traced paths) row for row over
ragged tiles of rows and of lanes, to the host's expansion through
``expand_seeds_counts``, and by the traced program's own text: the kernel's
one operand is the seeds. ``chip_smoke.py`` and ``scripts/chip_fold_step0.py``
hold the compiled kernel to the same bits on the chip.
"""

import numpy as np
import pytest

from sda_tpu import telemetry
from sda_tpu.ops import chacha, chacha_pallas, find_packed_parameters
from sda_tpu.ops.jaxcfg import ensure_x64

ensure_x64()

#: the cells' fields and a 46-bit one (k=5, t=2, n=8, parameter seed 0): one
#: modulus of each kind of prime ``mod_u64_const`` branches on
P31, P46, P61 = (
    int(find_packed_parameters(5, 2, 8, min_modulus_bits=bits, seed=0)[0]) for bits in (30, 45, 60)
)

#: rows below, at and over a tile of eight, and over two; blocks a row below,
#: just under, at and over a lane tile (700: two loop steps of one grid
#: step); every seed width and both counters ride along, each four times
ROWS, BLOCKS, WIDTHS = (1, 5, 9, 17), (1, 127, 128, 700), (1, 2, 4, 8)
CASES = [
    (rows, blocks, WIDTHS[(i + j) % 4], (0, 5)[(i + j // 2) % 2])
    for i, rows in enumerate(ROWS)
    for j, blocks in enumerate(BLOCKS)
]


def seeds_of(rows: int, width: int, salt: int = 0) -> np.ndarray:
    rng = np.random.default_rng(40 + 1000 * rows + 10 * width + salt)
    return rng.integers(0, 1 << 32, size=(rows, width), dtype=np.uint64).astype(np.uint32)


def blocks_by_path() -> dict:
    return {
        dict(labels)["path"]: value
        for (name, labels), value in telemetry.get_registry().snapshot()["counters"].items()
        if name == "sda_crypto_chacha_blocks_total"
    }


@pytest.fixture
def fresh_telemetry():
    telemetry.reset()
    yield
    telemetry.reset()


def test_the_cases_cover_every_width_and_counter():
    assert {(r, b) for r, b, _, _ in CASES} == {(r, b) for r in ROWS for b in BLOCKS}
    assert {w for *_, w, _ in CASES} == set(WIDTHS) and {c for *_, c in CASES} == {0, 5}


@pytest.mark.parametrize("rows,blocks,width,first_counter", CASES)
def test_kernel_keystream_is_the_hosts_row_for_row(rows, blocks, width, first_counter):
    import jax.numpy as jnp

    seeds = seeds_of(rows, width)
    got = np.asarray(chacha_pallas._rounds(jnp.asarray(seeds), blocks, first_counter, "interpret"))
    assert got.shape == (rows, blocks, 16) and got.dtype == np.uint32
    for row, seed in zip(got, seeds):
        assert np.array_equal(row, chacha.chacha_blocks(seed, first_counter, blocks))


@pytest.mark.parametrize("rows,blocks,width,first_counter", CASES[5::5])
def test_jnp_twin_is_the_hosts_too(rows, blocks, width, first_counter):
    import jax.numpy as jnp

    seeds = seeds_of(rows, width, salt=1)
    got = np.asarray(chacha_pallas._rounds(jnp.asarray(seeds), blocks, first_counter, "jnp"))
    want = np.stack([chacha.chacha_blocks(seed, first_counter, blocks) for seed in seeds])
    assert np.array_equal(got, want)


@pytest.mark.parametrize("modulus", [P31, P46, P61], ids=["p31", "p46", "p61"])
def test_expansion_on_the_interpreter_is_the_hosts(modulus):
    """Nine rows (a ragged second tile) of two-word seeds through the whole
    expansion: the kernel's keystream, the zone test, the compaction."""
    dim = 140
    seeds = seeds_of(9, 2, salt=modulus % 97)
    masks, counts = chacha_pallas.expand_seeds_counts(seeds, dim, modulus, "interpret")
    assert np.asarray(counts).min() >= dim
    for row, seed in zip(np.asarray(masks), seeds):
        assert np.array_equal(row, chacha.expand_seed(seed, dim, modulus))


def _all_avals(jaxpr, inside_kernel=False):
    """Every ``(shape, dtype, inside a pallas_call's body)`` of a jaxpr's
    variables, sub-jaxprs included, and its ``pallas_call`` equations."""
    from jax.extend.core import Literal

    avals, kernels = [], []
    for eqn in jaxpr.eqns:
        is_kernel = eqn.primitive.name == "pallas_call"
        if is_kernel:
            kernels.append(eqn)
        for v in [*eqn.invars, *eqn.outvars]:
            if not isinstance(v, Literal) and hasattr(v.aval, "shape"):
                avals.append((tuple(v.aval.shape), str(v.aval.dtype), inside_kernel))
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    more, more_kernels = _all_avals(inner, inside_kernel or is_kernel)
                    avals += more
                    kernels += more_kernels
    return avals, kernels


def test_the_kernels_only_operand_is_the_seeds_and_no_state_tensor_is_traced():
    import jax
    import jax.numpy as jnp

    rows, dim = 9, 300
    blocks = (chacha_pallas._window_pairs(dim, P61) * 2 + 15) // 16
    seeds = jnp.zeros((rows, 4), jnp.uint32)
    traced = jax.make_jaxpr(lambda s: chacha_pallas.expand_seeds_counts(s, dim, P61, "interpret"))(seeds)
    avals, kernels = _all_avals(traced.jaxpr)
    (rounds,) = [k for k in kernels if k.params["name"] == "chacha_rounds"]
    assert [(tuple(v.aval.shape), str(v.aval.dtype)) for v in rounds.invars] == [((rows, 8), "uint32")]
    assert [tuple(v.aval.shape) for v in rounds.outvars] == [(16, rows, blocks)]
    # outside the kernel a sixteen-word minor axis is the keystream in stream
    # order, once; the parent built, scattered into and transposed states too
    states = {a for a in avals if a[0][-1:] == (16,) and np.prod(a[0]) >= rows * blocks * 16}
    assert states == {((rows, blocks, 16), "uint32", False)}


def test_blocks_are_counted_by_path_once_a_trace(fresh_telemetry):
    import jax
    import jax.numpy as jnp

    dim = 300
    blocks = (chacha_pallas._window_pairs(dim, P61) * 2 + 15) // 16
    fold = jax.jit(chacha_pallas.expand_seeds_counts, static_argnums=(1, 2, 3))
    fold.lower(jnp.zeros((9, 4), jnp.uint32), dim, P61, "interpret")
    assert blocks_by_path() == {"interpret": 9 * blocks}
    fold.lower(jnp.zeros((5, 4), jnp.uint32), dim, P61, "jnp")
    # ``auto`` is what this process's backend runs: the jnp twin off a TPU
    fold.lower(jnp.zeros((2, 4), jnp.uint32), dim, P61, "auto")
    assert blocks_by_path() == {"interpret": 9 * blocks, "jnp": 7 * blocks}
    jax.make_jaxpr(lambda key: chacha_pallas.chacha_blocks_pallas(key, 5, 3, interpret=True))(
        jnp.zeros((8,), jnp.uint32)
    )
    assert blocks_by_path()["interpret"] == 9 * blocks + 3


@pytest.mark.parametrize("backend", ["auto", "pallas", "interpret", "jnp"])
@pytest.mark.parametrize("first_counter,blocks", [(0, 1 << 32), ((1 << 32) - 3, 3), (-1, 4)])
def test_a_counter_past_32_bits_is_refused_under_every_name(first_counter, blocks, backend, fresh_telemetry):
    import jax.numpy as jnp

    with pytest.raises(ValueError, match="32 bits"):
        chacha_pallas._rounds(jnp.zeros((2, 4), jnp.uint32), blocks, first_counter, backend)
    assert blocks_by_path() == {}


def test_the_last_block_under_the_bound_has_the_hosts_bits():
    import jax.numpy as jnp

    seed = seeds_of(1, 8, salt=2)
    first = (1 << 32) - 4
    for backend in ("interpret", "jnp"):
        got = np.asarray(chacha_pallas._rounds(jnp.asarray(seed), 3, first, backend))[0]
        assert np.array_equal(got, chacha.chacha_blocks(seed[0], first, 3)), backend
