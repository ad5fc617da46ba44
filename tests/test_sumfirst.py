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
