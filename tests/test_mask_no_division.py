"""No value on the mask's path is divided by p on the device (PR 36).

A v5e has no integer divide: ``u64 % p`` is a 64-step long division in
emulated 64-bit lanes. The expansion reduces its accepted draws by
``ops.modular.mod_u64_const`` and the recipient's halving sum corrects by a
conditional p: held here to the host's expansion row for row, to the plain
reference's counts, to the ``lax.rem`` tree it replaces bit for bit, and by
the lowered text of both of the masked round's programs. The last test counts
the programs a recipient's ``combine`` compiles: a warm-up that loads more
than it did shows there first.
"""

import numpy as np
import pytest

from benchmark import reference_chacha  # conftest puts the checkout on the path
from sda_tpu.ops import chacha, chacha_pallas, find_packed_parameters
from sda_tpu.ops.jaxcfg import ensure_x64
from sda_tpu.protocol import ChaChaMasking, PackedShamirSharing

ensure_x64()

#: the cells' fields and a 46-bit one (k=5, t=2, n=8, parameter seed 0)
FIELDS = {bits: find_packed_parameters(5, 2, 8, min_modulus_bits=bits, seed=0) for bits in (30, 45, 60)}
P31, P46, P61 = (int(FIELDS[bits][0]) for bits in (30, 45, 60))
P62 = (1 << 62) - 57  # the largest prime the halving sum and the mask's add admit

#: one modulus of each kind ``mod_u64_const`` branches on: a power of two
#: below 2^32 + 1, a prime below 2^32, one whose reciprocal takes 32 bits,
#: one whose reciprocal takes 16, and the largest power of two
KINDS = {"2^32": 1 << 32, "p31": P31, "p46": P46, "p61": P61, "2^63": 1 << 63}


def test_the_moduli_are_one_of_each_kind():
    assert (P31.bit_length(), P46.bit_length(), P61.bit_length()) == (31, 46, 61)
    assert 33 <= P46.bit_length() < 50 <= P61.bit_length()  # ``e`` = bits - 33 | bits - 49


@pytest.mark.parametrize("backend", ["jnp", "interpret"])
@pytest.mark.parametrize("kind", list(KINDS))
def test_expansion_is_the_hosts_row_for_row(kind, backend):
    """``expand_seeds_counts`` against ``ops.chacha.expand_seed`` (the host's,
    bit-exact to rand 0.3) and the plain reference's masks and counts. At 150
    the window fills a lane tile, so ``interpret`` compacts in the kernel."""
    m, dim = KINDS[kind], 150
    rng = np.random.default_rng(m % (1 << 32))
    seeds = rng.integers(0, 1 << 32, size=(6, 4), dtype=np.uint64).astype(np.uint32)
    masks, counts = chacha_pallas.expand_seeds_counts(seeds, dim, m, backend)
    masks, counts = np.asarray(masks), np.asarray(counts)
    assert masks.dtype == np.int64 and counts.dtype == np.int32
    want, want_counts = reference_chacha.expand(seeds, dim, m, chacha_pallas._window_pairs(dim, m))
    assert np.array_equal(counts, want_counts) and counts.min() >= dim
    assert np.array_equal(masks, want)
    for row, seed in zip(masks, seeds):
        assert np.array_equal(row, chacha.expand_seed(seed, dim, m))
    assert masks.min() >= 0 and masks.max() < m and masks.max() > m // 2


def _names_a_division(text: str) -> list:
    return [w for w in ("divide", "remainder") if w in text]


@pytest.mark.parametrize("modulus", [P46, P61, P62], ids=["p46", "p61", "p62"])
def test_the_recipients_fold_lowers_to_no_division(modulus):
    import jax.numpy as jnp

    seeds = jnp.zeros((5, 4), jnp.uint32)
    lowered = chacha_pallas.fold_chunk_jit().lower(seeds, 150, modulus, "jnp")
    assert _names_a_division(lowered.as_text()) == []
    assert _names_a_division(lowered.compile().as_text()) == []


@pytest.mark.parametrize("bits", [30, 45, 60])
def test_a_masked_step_lowers_to_no_division(bits):
    """The mask stage in front of the sum-first entry, as ``c5-masked``'s
    step has it (``benchmark/rounds/masked_fold.py``), at each field."""
    import jax
    import jax.numpy as jnp

    from sda_tpu.parallel import masked
    from sda_tpu.parallel.engine import make_plan
    from sda_tpu.parallel.sumfirst import value_limb_sums_chunk

    p, w2, w3 = FIELDS[bits]
    plan = make_plan(PackedShamirSharing(5, 8, 2, p, w2, w3), 150)
    chunk_fn = masked.masked_chunk(value_limb_sums_chunk, plan, ChaChaMasking(int(p), 150, 128))

    def masked_step(acc, chunk, key, i):
        out, seeds, counts = chunk_fn(chunk, jax.random.fold_in(key, i))
        return acc + out, seeds, counts

    chunk = jnp.zeros((4, 150), jnp.int32 if bits == 30 else jnp.int64)
    acc = jax.eval_shape(lambda c, k: chunk_fn(c, k)[0], chunk, jax.random.key(0))
    lowered = jax.jit(masked_step).lower(acc, chunk, jax.random.key(0), 0)
    assert _names_a_division(lowered.as_text()) == []
    assert _names_a_division(lowered.compile().as_text()) == []


def _rem_tree(x, m):
    """``mod_sum_wide_jnp`` as it was: ``lax.rem`` at every level."""
    import jax.numpy as jnp
    from jax import lax

    x = jnp.asarray(x, jnp.int64)
    n = x.shape[0]
    levels = max(1, (n - 1).bit_length())
    x = jnp.pad(x, ((0, (1 << levels) - n),) + ((0, 0),) * (x.ndim - 1))
    for _ in range(levels):
        half = x.shape[0] // 2
        x = lax.rem(x[:half] + x[half:], jnp.int64(m))
    return x[0]


@pytest.mark.parametrize("rows", [1, 2, 7, 8, 33, 64])
@pytest.mark.parametrize("sign", ["non-negative", "negative", "mixed"])
@pytest.mark.parametrize("m", [433, P31, P61, P62], ids=["433", "p31", "p61", "p62"])
def test_halving_sum_is_the_rem_tree_bit_for_bit(m, sign, rows):
    """Signs included: a pair sum in (-2m, 2m) less or plus one m is what the
    truncated remainder gives. Columns of extremes put sums on ±(2m-2)."""
    from sda_tpu.ops.modular import mod_sum_wide_jnp

    rng = np.random.default_rng(rows * 1000 + m % 997)
    low, high = {"non-negative": (0, m), "negative": (-m + 1, 1), "mixed": (-m + 1, m)}[sign]
    x = rng.integers(low, high, size=(rows, 40), dtype=np.int64)
    x[:, 0] = high - 1
    x[:, 1] = low
    x[::2, 2], x[1::2, 2] = high - 1, low
    x[:, 3] = 0
    got, want = np.asarray(mod_sum_wide_jnp(x, m, axis=0)), np.asarray(_rem_tree(x, m))
    assert got.dtype == np.int64
    np.testing.assert_array_equal(got, want)
    exact = np.array([int(v) % m for v in x.astype(object).sum(axis=0)], dtype=np.int64)
    np.testing.assert_array_equal(np.mod(got, m), exact)
    assert np.array_equal(np.asarray(mod_sum_wide_jnp(x.T, m, axis=1)), got)


@pytest.mark.parametrize("m", [433, P31, P61, P62], ids=["433", "p31", "p61", "p62"])
def test_halving_sum_on_the_edges_of_a_correction(m):
    """Pair sums of exactly ±m, ±(m-1), ±(2m-2) and 0."""
    from sda_tpu.ops.modular import mod_sum_wide_jnp

    x = np.array([[m - 1, 1 - m, m - 2, 2 - m, m - 1, 1 - m, m - 1, 0],
                  [1, -1, 1, -1, m - 1, 1 - m, 1 - m, 0]], dtype=np.int64)  # fmt: skip
    got = np.asarray(mod_sum_wide_jnp(x, m, axis=0))
    np.testing.assert_array_equal(got, np.asarray(_rem_tree(x, m)))
    np.testing.assert_array_equal(got, [0, 0, m - 1, 1 - m, m - 2, 2 - m, 0, 0])


#: what a recipient's first ``combine`` of two folds compiles on the CPU after
#: ``jax.clear_caches()``: ``jit(_fold_chunk)`` and the eager programs around
#: it in ``combine_masks_device``: ``convert_element_type`` and
#: ``broadcast_in_dim`` (the zero total), ``_reduce_min`` (the counts' check),
#: ``add`` and ``remainder`` (``(total + part) % m``, over ``dim`` values a
#: fold). A change that leaves an operation outside the fold, or splits the
#: fold, moves this number: then read what a warm-up on the chip loads
#: (``scripts/setup_events.py``).
COMBINE_PROGRAMS = 6


def test_a_combine_compiles_a_fixed_number_of_programs_and_then_none(monkeypatch):
    import jax
    import jax.monitoring

    from sda_tpu.crypto.masking import ChaChaMasker

    compiled = []
    listening = [True]

    def on_event(name, *_a, **_kw):
        if listening[0] and "backend_compile" in name:
            compiled.append(name)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    monkeypatch.setattr(ChaChaMasker, "DEVICE_COMBINE_THRESHOLD", 1)
    masker = ChaChaMasker(P61, 150, 128)
    rng = np.random.default_rng(36)
    uploads = list(rng.integers(0, 1 << 32, size=(6, 4), dtype=np.int64))
    try:
        jax.clear_caches()
        first = masker.combine(uploads, chunk=3)
        n_first = len(compiled)
        again = masker.combine(uploads, chunk=3)
        n_again = len(compiled) - n_first
    finally:
        listening[0] = False
    assert np.array_equal(first, again)
    assert np.array_equal(first, reference_chacha.mask_sum(np.stack(uploads), 150, P61))
    assert n_again == 0
    assert n_first == COMBINE_PROGRAMS
