"""Sum-first clerk sums (parallel/sumfirst.py): linearity restructure parity.

The per-participant path (share matmul per participant, then clerk-combine)
and the sum-first path (participant sum, then one share matmul) must produce
*bit-identical* clerk sums for the same PRNG key — both consume randomness
via the same ``_device_randomness(key, (C, B, t), p)`` call, and matmul
commutes with the participant sum over the field.
"""

import numpy as np
import pytest

from sda_tpu.ops import find_packed_parameters
from sda_tpu.ops.modular import positive
from sda_tpu.protocol import PackedShamirSharing

PACKED = PackedShamirSharing(3, 8, 4, 433, 354, 150)


@pytest.fixture(scope="module")
def jax_mods():
    import jax

    from sda_tpu.ops.jaxcfg import ensure_x64

    ensure_x64()
    return jax


def _wide_scheme():
    p, w2, w3 = find_packed_parameters(3, 4, 8, min_modulus_bits=60, seed=1)
    return PackedShamirSharing(3, 8, 4, p, w2, w3)


@pytest.mark.parametrize("scheme_fn", [lambda: PACKED, _wide_scheme], ids=["p433", "wide61"])
def test_bit_identical_to_per_participant_path(jax_mods, scheme_fn):
    import jax.numpy as jnp
    from jax import lax, random

    from sda_tpu.parallel import clerk_sums_sum_first
    from sda_tpu.parallel.engine import clerk_combine, make_plan, share_participants

    scheme = scheme_fn()
    p = scheme.prime_modulus
    dim = 14  # pad path: 14 = 3*4 + 2
    plan = make_plan(scheme, dim)
    rng = np.random.default_rng(3)
    secrets = rng.integers(p - 100, p, size=(21, dim)).astype(np.int64)
    key = random.key(5)

    got = clerk_sums_sum_first(jnp.asarray(secrets), key, plan)

    if p < (1 << 31):
        shares = share_participants(jnp.asarray(secrets), key, plan)
        want = np.asarray(lax.rem(clerk_combine(shares), jnp.int64(p)))
        want = positive(want, p)
    else:
        from sda_tpu.parallel.engine import share_combine_limb
        from sda_tpu.parallel.limbmatmul import limb_recombine_host

        acc = share_combine_limb(jnp.asarray(secrets), key, plan)
        want = limb_recombine_host(np.asarray(acc), p).T  # (n, B) canonical

    np.testing.assert_array_equal(got, np.asarray(want))


@pytest.mark.parametrize("scheme_fn", [lambda: PACKED, _wide_scheme], ids=["p433", "wide61"])
def test_chunked_accumulation_reconstructs_plain_sum(jax_mods, scheme_fn):
    """The streaming shape the bench drives: accumulate exact limb sums over
    chunks with plain +, one host epilogue, reconstruct from a dropout
    subset, verify against exact python-int plain sums."""
    import jax.numpy as jnp
    from jax import random

    from sda_tpu.parallel.engine import make_plan
    from sda_tpu.parallel.sumfirst import (
        clerk_sums_from_limb_acc,
        reconstruct_from_clerk_sums,
        value_limb_sums_chunk,
    )

    scheme = scheme_fn()
    p = scheme.prime_modulus
    dim = 9
    plan = make_plan(scheme, dim)
    rng = np.random.default_rng(11)
    chunks = [rng.integers(0, p, size=(13, dim)).astype(np.int64) for _ in range(4)]

    acc = None
    for i, chunk in enumerate(chunks):
        s = np.asarray(value_limb_sums_chunk(jnp.asarray(chunk), random.key(i), plan))
        acc = s if acc is None else acc + s

    clerk_sums, vsums = clerk_sums_from_limb_acc(acc, plan)
    out = reconstruct_from_clerk_sums(
        clerk_sums, list(range(scheme.reconstruction_threshold)), scheme, dim
    )

    allsec = np.concatenate(chunks, axis=0)
    want = np.array(
        [sum(int(v) for v in allsec[:, j]) % p for j in range(dim)], dtype=np.int64
    )
    np.testing.assert_array_equal(positive(np.asarray(out), p), want)
    # the value-sum secret columns are the plain batched sums (free check)
    k = scheme.secret_count
    np.testing.assert_array_equal(vsums[:, :k].reshape(-1)[:dim], want)


def test_rejects_oversized_chunk(jax_mods):
    from sda_tpu.parallel.engine import make_plan
    from sda_tpu.parallel.sumfirst import MAX_PARTICIPANTS, clerk_sums_sum_first

    plan = make_plan(PACKED, 3)

    class FakeShaped:
        shape = (MAX_PARTICIPANTS + 1, 3)

    with pytest.raises(ValueError):
        clerk_sums_sum_first(FakeShaped(), None, plan)


def test_exact_sum_narrow_matches_int64(jax_mods):
    """The int32 narrow reduction must equal plain int64 sums exactly,
    including at the value bound (2^31 - 1) and the row bound (2^15)."""
    import jax.numpy as jnp

    from sda_tpu.parallel.sumfirst import MAX_NARROW_CHUNK, exact_sum_narrow

    rng = np.random.default_rng(5)
    x = rng.integers(0, (1 << 31) - 1, size=(257, 33), dtype=np.int64)
    x[0, :] = (1 << 31) - 1  # boundary values
    got = np.asarray(exact_sum_narrow(jnp.asarray(x)))
    np.testing.assert_array_equal(got, x.sum(axis=0))

    # worst case: max rows, all at the max value — the int32 limb bound
    worst = np.full((MAX_NARROW_CHUNK, 3), (1 << 31) - 1, dtype=np.int64)
    got = np.asarray(exact_sum_narrow(jnp.asarray(worst)))
    np.testing.assert_array_equal(got, worst.sum(axis=0))

    with pytest.raises(ValueError, match="narrow reduction bound"):
        exact_sum_narrow(jnp.zeros((MAX_NARROW_CHUNK + 1, 2), dtype=jnp.int32))


def test_exact_sum_narrow_takes_the_uint32_halves_of_a_wide_value(jax_mods):
    """uint32 values are summed as they are, bit 31 included: what a wide
    value's (hi, lo) halves need (ROADMAP S4), at the row bound too."""
    import jax.numpy as jnp

    from sda_tpu.parallel.sumfirst import MAX_NARROW_CHUNK, exact_sum_narrow

    values = np.random.default_rng(6).integers(0, 1 << 61, size=(257, 9))
    values[0, :] = (1 << 61) - 1
    lo = (values & 0xFFFFFFFF).astype(np.uint32)
    hi = (values >> 32).astype(np.uint32)
    joined = np.asarray(exact_sum_narrow(jnp.asarray(lo))).astype(object) + (
        np.asarray(exact_sum_narrow(jnp.asarray(hi))).astype(object) << 32
    )
    assert joined.tolist() == [sum(int(v) for v in col) for col in values.T]

    worst = np.full((MAX_NARROW_CHUNK, 3), 0xFFFFFFFF, dtype=np.uint32)
    got = np.asarray(exact_sum_narrow(jnp.asarray(worst)))
    np.testing.assert_array_equal(got, worst.astype(np.int64).sum(axis=0))


def _reference_limb_sums(secrets, key, plan):
    """``(2, B, K)`` python-integer limb sums of ``[batched secrets | the
    program's draw]``, the draw in the ``(C, B, t)`` shape every other engine
    draws it in: what ``value_limb_sums_chunk`` must return at a two-limb
    modulus, element for element."""
    from sda_tpu.parallel.engine import _device_randomness

    C, dim = secrets.shape
    k = plan.input_size
    nb = -(-dim // k)
    padded = np.zeros((C, nb * k), dtype=object)
    padded[:, :dim] = secrets.astype(object)
    draws = np.asarray(_device_randomness(key, (C, nb, plan.rand_size), plan.modulus))
    values = np.concatenate([padded.reshape(C, nb, k), draws.astype(object)], axis=-1)
    return np.stack([(values & 0xFFFFFFFF).sum(axis=0), (values >> 32).sum(axis=0)])


def _wide_case(case, p):
    """``(rows, dim, fill)``: the chunk of one case; no ``fill`` means
    uniform values over the field."""
    from sda_tpu.parallel.sumfirst import MAX_NARROW_CHUNK

    return {
        # dim 14 = 2 * 5 + 4: the last batch is padded
        "uniform": (37, 14, None),
        # every value the field's largest: both halves' sums at their most
        "all_p_minus_1": (37, 14, p - 1),
        # low word all ones under the largest canonical high word but one:
        # bit 31 of the low half set in every row, every 16-bit quarter full
        "low_half_all_ones": (37, 14, (((p >> 32) - 1) << 32) | 0xFFFFFFFF),
        # the int32 bound's edge: C * 65 535 < 2^31 holds with 32 768 to spare
        "max_narrow_rows": (MAX_NARROW_CHUNK, 3, p - 1),
        # one row more: the int64 road, to the same sums
        "one_row_over": (MAX_NARROW_CHUNK + 1, 3, p - 1),
    }[case]


@pytest.mark.parametrize(
    "case", ["uniform", "all_p_minus_1", "low_half_all_ones", "max_narrow_rows", "one_row_over"]
)
def test_wide_limb_sums_equal_python_integers(jax_mods, case):
    """The accumulator of a 61-bit plan, whichever road sums it, is the exact
    integer it always was: limb 0 = Σ (v & 2³²−1), limb 1 = Σ (v ≫ 32)."""
    import jax.numpy as jnp
    from jax import random

    from sda_tpu.parallel.engine import make_plan
    from sda_tpu.parallel.sumfirst import MAX_NARROW_CHUNK, limb_sum_road, value_limb_sums_chunk

    scheme = _wide_scheme()
    p = scheme.prime_modulus
    rows, dim, fill = _wide_case(case, p)
    plan = make_plan(scheme, dim)
    assert limb_sum_road(p, rows) == ("halves32" if rows <= MAX_NARROW_CHUNK else "int64")
    if fill is None:
        secrets = np.random.default_rng(31).integers(0, p, size=(rows, dim)).astype(np.int64)
    else:
        secrets = np.full((rows, dim), fill, dtype=np.int64)
    key = random.key(9)
    got = np.asarray(value_limb_sums_chunk(jnp.asarray(secrets), key, plan))
    assert got.dtype == np.int64 and got.shape == (2, -(-dim // plan.input_size), plan.input_size + plan.rand_size)
    assert got.astype(object).tolist() == _reference_limb_sums(secrets, key, plan).tolist()


@pytest.mark.parametrize("scheme_fn", [lambda: PACKED, _wide_scheme], ids=["p433", "wide61"])
def test_sharded_entry_equals_the_single_chip_chunks(jax_mods, scheme_fn):
    """Over the CPU mesh (p=4, d=1) the sharded entry is the sum of the
    single-chip chunk over each chip's rows, under that chip's key."""
    import jax.numpy as jnp
    from jax import random
    from jax.sharding import Mesh

    from sda_tpu.parallel.engine import make_plan
    from sda_tpu.parallel.sumfirst import sharded_value_limb_sums, value_limb_sums_chunk

    jax = jax_mods
    scheme = scheme_fn()
    p = scheme.prime_modulus
    dim = 15
    plan = make_plan(scheme, dim)
    secrets = np.random.default_rng(12).integers(0, p, size=(24, dim)).astype(np.int64)
    secrets[0, :] = p - 1
    key = random.key(4)
    mesh = Mesh(np.array(jax.devices("cpu")[:4]).reshape(4, 1), ("p", "d"))
    got = np.asarray(sharded_value_limb_sums(plan, mesh)(jnp.asarray(secrets), key))
    want = sum(
        np.asarray(
            value_limb_sums_chunk(
                jnp.asarray(secrets[6 * i : 6 * i + 6]),
                random.fold_in(random.fold_in(key, i), 0),
                plan,
            )
        )
        for i in range(4)
    )
    np.testing.assert_array_equal(got, want)


def _roads_counted():
    """Limb-sum reductions traced since the last reset, by road."""
    from sda_tpu import telemetry

    return {
        dict(labels)["road"]: value
        for (name, labels), value in telemetry.get_registry().snapshot()["counters"].items()
        if name == "sda_limb_sum_roads_total"
    }


@pytest.mark.parametrize(
    "bits,rows,road",
    [(31, 6, "int32"), (61, 6, "halves32"), (61, (1 << 15) + 1, "int64"), (31, (1 << 15) + 1, "int64")],
)
def test_the_counter_names_the_road_of_each_traced_limb_sum(jax_mods, bits, rows, road):
    """``sda_limb_sum_roads_total{road}``: one count for each of the two
    ``limb_sums`` calls (secrets, randomness) of a trace, none for a step of
    a program already traced."""
    import jax.numpy as jnp
    from jax import random

    from sda_tpu import telemetry
    from sda_tpu.parallel.engine import make_plan
    from sda_tpu.parallel.sumfirst import limb_sum_road, value_limb_sums_chunk

    jax = jax_mods
    scheme = PACKED if bits == 31 else _wide_scheme()
    plan = make_plan(scheme, 4)
    assert limb_sum_road(plan.modulus, rows) == road
    step = jax.jit(lambda s, k: value_limb_sums_chunk(s, k, plan))
    secrets = jnp.zeros((rows, 4), jnp.int64)
    telemetry.reset()
    try:
        step(secrets, random.key(0))
        assert _roads_counted() == {road: 2}
        step(secrets, random.key(1))  # the same program: nothing is traced
        assert _roads_counted() == {road: 2}
    finally:
        telemetry.reset()


def _equations(jaxpr):
    """Every equation of a jaxpr, those of its sub-jaxprs (pjit, custom
    calls) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


@pytest.mark.parametrize("bits,rows,wide_reductions", [(61, 40, 0), (31, 40, 0), (61, (1 << 15) + 1, 4)])
def test_no_reduction_takes_a_64_bit_tensor_of_all_the_rows(jax_mods, bits, rows, wide_reductions):
    """The structure that is the speed: up to ``MAX_NARROW_CHUNK`` rows
    nothing of C rows is reduced at 64 bits, at either width; the oversized
    chunk's int64 road is seen by the same count (two limbs, two callers)."""
    import jax.numpy as jnp
    from jax import random

    from sda_tpu.parallel.engine import make_plan
    from sda_tpu.parallel.sumfirst import value_limb_sums_chunk

    jax = jax_mods
    scheme = PACKED if bits == 31 else _wide_scheme()
    plan = make_plan(scheme, 14)
    closed = jax.make_jaxpr(lambda s, k: value_limb_sums_chunk(s, k, plan))(
        jax.ShapeDtypeStruct((rows, 14), jnp.int64), random.key(0)
    )
    reductions = [
        v.aval
        for eqn in _equations(closed.jaxpr)
        if eqn.primitive.name in ("reduce_sum", "reduce")
        for v in eqn.invars
        if v.aval.shape and v.aval.shape[0] == rows
    ]
    assert reductions, "the chunk step reduces over its rows"
    assert sum(a.dtype.itemsize == 8 for a in reductions) == wide_reductions, reductions
