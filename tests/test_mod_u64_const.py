"""The default draw's field reduction (ops/modular.mod_u64_const): exact
against python integers for every kind of modulus, and ``uniform_mod_device``
bit for bit what ``u64 % m`` gave, with no division in what it lowers to.

The benchmark's ``correct`` cannot see the share randomness (it cancels in
the reveal), so these tests are what holds the draw's distribution still.
"""

import numpy as np
import pytest

from sda_tpu.ops import find_packed_parameters
from sda_tpu.protocol import PackedShamirSharing

#: the two cells' fields (benchmark/configs: k=5, t=2, n=8, parameter seed 0)
P31, W31, V31 = find_packed_parameters(5, 2, 8, min_modulus_bits=30, seed=0)
P61, W61, V61 = find_packed_parameters(5, 2, 8, min_modulus_bits=60, seed=0)

MODULI = [
    1, 2, 3, (1 << 31) - 1, 1 << 31, (1 << 32) - 5, (1 << 32) - 1, 1 << 32,
    (1 << 32) + 1, (1 << 32) + 15, (1 << 33) + 17, (1 << 46) - 21,
    (1 << 49) - 1, (1 << 49) + 9, 65537, 40961, P31, P61, (1 << 61) - 1,
    (1 << 62) + 1, (1 << 63) - 25, 1 << 63,
]  # fmt: skip


@pytest.fixture(scope="module")
def jax_mods():
    import jax

    from sda_tpu.ops.jaxcfg import ensure_x64

    ensure_x64()
    return jax


def _words(u):
    u = np.asarray(u, dtype=np.uint64)
    return (u >> np.uint64(32)).astype(np.uint32), (u & np.uint64(0xFFFFFFFF)).astype(np.uint32)


@pytest.mark.parametrize("m", MODULI, ids=lambda m: f"{m.bit_length()}b-{m:#x}")
def test_reducer_equals_python_integers(jax_mods, m):
    from sda_tpu.ops.modular import mod_u64_const

    top = (1 << 64) // m * m
    edges = [0, 1, m - 1, m, m + 1, (1 << 64) - 1, top - 1, top, 1 << 32, (1 << 32) - 1]
    # around multiples of m, where a quotient estimate one or two short shows
    edges += [q * m + d for q in (2, 3, top // m // 2, top // m - 1) for d in (-1, 0, 1)]
    rng = np.random.default_rng(m % (1 << 32))
    multiples = rng.integers(0, min(top // m, 1 << 63), size=2000, dtype=np.uint64)
    edges += [int(q) * m + d for q in multiples for d in (-1, 0)]
    u = np.concatenate([
        np.array([x % (1 << 64) for x in edges], dtype=np.uint64),
        rng.integers(0, 1 << 64, size=100_000, dtype=np.uint64, endpoint=False),
    ])  # fmt: skip
    got = np.asarray(jax_mods.jit(mod_u64_const, static_argnums=2)(*_words(u), m))
    want = np.array([x % m for x in u.tolist()], dtype=np.uint64)
    assert got.dtype == np.uint64
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("m", [0, -3, (1 << 63) + 1])
def test_reducer_refuses_a_modulus_out_of_range(jax_mods, m):
    from sda_tpu.ops.modular import mod_u64_const

    with pytest.raises(ValueError, match="modulus out of range"):
        mod_u64_const(np.zeros(2, np.uint32), np.zeros(2, np.uint32), m)


def _old_uniform_mod_device(key, shape, m):
    """``uniform_mod_device`` as it was before the reducer: the emulated ``%``."""
    import jax.numpy as jnp
    from jax import random

    hi = random.bits(key, shape=shape, dtype=jnp.uint32)
    lo = random.bits(random.fold_in(key, 1), shape=shape, dtype=jnp.uint32)
    u64 = (hi.astype(jnp.uint64) << 32) | lo.astype(jnp.uint64)
    return (u64 % jnp.uint64(m)).astype(jnp.int64)


@pytest.mark.parametrize("seed", [0, 7, 2400000123])
@pytest.mark.parametrize("m", [P31, P61], ids=["p31", "p61"])
def test_uniform_mod_device_is_the_remainder_of_the_same_two_draws(jax_mods, m, seed):
    import jax.numpy as jnp
    from jax import random

    from sda_tpu.ops.rng import uniform_mod_device

    key, shape = random.key(seed), (13, 11, 2)
    hi = np.asarray(random.bits(key, shape=shape, dtype=jnp.uint32)).astype(np.uint64)
    lo = np.asarray(random.bits(random.fold_in(key, 1), shape=shape, dtype=jnp.uint32))
    want = ((hi << np.uint64(32) | lo.astype(np.uint64)) % np.uint64(m)).astype(np.int64)
    got = uniform_mod_device(key, shape, m)
    assert got.dtype == jnp.int64 and got.shape == shape
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(np.asarray(_old_uniform_mod_device(key, shape, m)), want)


def _plan(wide):
    from sda_tpu.parallel.engine import make_plan

    scheme = PackedShamirSharing(5, 8, 2, *((P61, W61, V61) if wide else (P31, W31, V31)))
    return make_plan(scheme, 23)  # 23 = 4*5 + 3: the padded tail too


def _engines():
    from sda_tpu.parallel.engine import share_combine_limb
    from sda_tpu.parallel.sumfirst import value_limb_sums_chunk

    return {"value_limb_sums_chunk": value_limb_sums_chunk, "share_combine_limb": share_combine_limb}


@pytest.mark.parametrize("wide", [False, True], ids=["p31", "p61"])
@pytest.mark.parametrize("engine", ["value_limb_sums_chunk", "share_combine_limb"])
def test_engines_fold_what_they_folded_under_the_old_remainder(jax_mods, monkeypatch, engine, wide):
    import jax.numpy as jnp
    from jax import random

    from sda_tpu.ops import rng as rng_mod

    plan = _plan(wide)
    p = plan.modulus
    secrets = np.random.default_rng(5).integers(0, p, size=(17, plan.dim))
    secrets = jnp.asarray(secrets, dtype=jnp.int64 if wide else jnp.int32)
    fold = _engines()[engine]
    new = np.asarray(fold(secrets, random.key(11), plan))
    # the engines look the draw up when they trace, so this reaches them
    calls = []

    def old_draw(key, shape, m):
        calls.append(shape)
        return _old_uniform_mod_device(key, shape, m)

    monkeypatch.setattr(rng_mod, "uniform_mod_device", old_draw)
    old = np.asarray(fold(secrets, random.key(11), plan))
    # one draw a step; the sum-first engine draws a wide field's (C, B, t)
    # flat, the same values (sumfirst.value_limb_sums_chunk, `halves32`)
    flat = wide and engine == "value_limb_sums_chunk"
    assert calls == [(17, 10) if flat else (17, 5, 2)] and new.any()
    np.testing.assert_array_equal(new, old)


@pytest.mark.parametrize("m", [P31, P61, (1 << 46) - 21], ids=["p31", "p61", "46b"])
def test_the_lowered_draw_holds_no_division(jax_mods, m):
    from jax import random

    from sda_tpu.ops.rng import uniform_mod_device

    draw = jax_mods.jit(uniform_mod_device, static_argnums=(1, 2))
    text = draw.lower(random.key(0), (8, 6, 2), m).as_text()
    assert "multiply" in text  # the reciprocal is there
    for op in ("remainder", "divide"):
        assert op not in text, op
    old = jax_mods.jit(_old_uniform_mod_device, static_argnums=(1, 2))
    assert "remainder" in old.lower(random.key(0), (8, 6, 2), m).as_text()
