"""TPU aggregation fabric tests on the virtual 8-device CPU mesh."""

import numpy as np
import pytest

from sda_tpu.ops.modular import positive
from sda_tpu.protocol import AdditiveSharing, PackedShamirSharing

PACKED = PackedShamirSharing(3, 8, 4, 433, 354, 150)
ADDITIVE = AdditiveSharing(share_count=3, modulus=433)


@pytest.fixture(scope="module")
def jax_mods():
    import jax

    from sda_tpu.ops.jaxcfg import ensure_x64

    ensure_x64()
    return jax


def _plain_sum(secrets, p):
    return (secrets.astype(np.int64).sum(axis=0)) % p


@pytest.mark.parametrize("scheme", [PACKED, ADDITIVE], ids=["packed", "additive"])
def test_single_device_secure_sum(jax_mods, scheme):
    import jax.numpy as jnp
    from jax import random

    from sda_tpu.parallel import TpuAggregator

    p = scheme.prime_modulus if isinstance(scheme, PackedShamirSharing) else scheme.modulus
    dim = 10
    rng = np.random.default_rng(0)
    secrets = rng.integers(0, p, size=(17, dim))
    agg = TpuAggregator(scheme, dim)
    out = agg.secure_sum(jnp.asarray(secrets), random.key(0))
    got = positive(np.asarray(out), p)
    np.testing.assert_array_equal(got, _plain_sum(secrets, p))


def test_single_device_dropout(jax_mods):
    import jax.numpy as jnp
    from jax import random

    from sda_tpu.parallel import TpuAggregator

    p = PACKED.prime_modulus
    dim = 7  # pad + truncate path
    rng = np.random.default_rng(1)
    secrets = rng.integers(0, p, size=(5, dim))
    agg = TpuAggregator(PACKED, dim)
    out = agg.secure_sum(
        jnp.asarray(secrets), random.key(1), indices=[0, 2, 3, 4, 5, 6, 7]
    )
    got = positive(np.asarray(out), p)
    np.testing.assert_array_equal(got, _plain_sum(secrets, p))


def test_limb_modmatmul_exact(jax_mods):
    import jax.numpy as jnp

    from sda_tpu.parallel.limbmatmul import limb_modmatmul

    p = (1 << 31) - 1  # worst-case width (Mersenne prime)
    rng = np.random.default_rng(2)
    A = rng.integers(0, p, size=(33, 20), dtype=np.int64)
    B = rng.integers(0, p, size=(20, 9), dtype=np.int64)
    got = np.asarray(limb_modmatmul(jnp.asarray(A), jnp.asarray(B), p))
    # exact reference with python ints
    want = (A.astype(object) @ B.astype(object)) % p
    np.testing.assert_array_equal(got, want.astype(np.int64))


def test_limb_modmatmul_const_exact(jax_mods):
    """Const-folded limb matmul (weight-folded B, single final rem) is
    exact at worst-case width, including against the generic limb path."""
    import jax.numpy as jnp

    from sda_tpu.parallel.limbmatmul import (
        fold_const_limbs,
        limb_modmatmul,
        limb_modmatmul_const,
        limb_partials_const,
        limb_recombine_host,
    )

    p = (1 << 31) - 1
    rng = np.random.default_rng(12)
    A = rng.integers(0, p, size=(33, 20), dtype=np.int64)
    B = rng.integers(0, p, size=(20, 9), dtype=np.int64)
    want = ((A.astype(object) @ B.astype(object)) % p).astype(np.int64)
    got = np.asarray(limb_modmatmul_const(jnp.asarray(A), B, p))
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        got, np.asarray(limb_modmatmul(jnp.asarray(A), jnp.asarray(B), p))
    )
    # wide modulus: partials + host recombine stays exact
    pw = (1 << 61) - 1  # Mersenne prime
    Aw = rng.integers(0, pw, size=(9, 6), dtype=np.int64)
    Bw = rng.integers(0, pw, size=(6, 4), dtype=np.int64)
    stacks = fold_const_limbs(Bw, pw)
    partials = np.asarray(limb_partials_const(jnp.asarray(Aw), stacks, pw))
    got_w = limb_recombine_host(partials, pw)
    want_w = ((Aw.astype(object) @ Bw.astype(object)) % pw).astype(np.int64)
    np.testing.assert_array_equal(got_w, want_w)


def test_limb_path_matches_int64_path(jax_mods):
    import jax.numpy as jnp
    from jax import random

    from sda_tpu.parallel import TpuAggregator

    p = PACKED.prime_modulus
    dim = 30
    rng = np.random.default_rng(3)
    secrets = rng.integers(0, p, size=(9, dim))
    out_a = TpuAggregator(PACKED, dim, use_limbs=False).secure_sum(
        jnp.asarray(secrets), random.key(7)
    )
    out_b = TpuAggregator(PACKED, dim, use_limbs=True).secure_sum(
        jnp.asarray(secrets), random.key(7)
    )
    np.testing.assert_array_equal(
        positive(np.asarray(out_a), p), positive(np.asarray(out_b), p)
    )


def test_wide_modulus_limb_pipeline(jax_mods):
    """61-bit modulus: fused limb share+combine on device, exact host
    recombine of the tiny accumulator, host reconstruction."""
    import jax.numpy as jnp
    from jax import lax, random

    from sda_tpu.ops import find_packed_parameters
    from sda_tpu.ops.modular import mod_sum_wide_jnp
    from sda_tpu.parallel.engine import make_plan, reconstruct, share_combine_limb
    from sda_tpu.parallel.limbmatmul import limb_recombine_host

    p, w2, w3 = find_packed_parameters(3, 4, 8, min_modulus_bits=60, seed=1)
    scheme = PackedShamirSharing(3, 8, 4, p, w2, w3)
    dim = 12
    plan = make_plan(scheme, dim)
    rng = np.random.default_rng(7)
    secrets = rng.integers(p - 50, p, size=(40, dim)).astype(np.int64)

    acc = share_combine_limb(jnp.asarray(secrets), random.key(0), plan)
    acc = lax.rem(acc, jnp.int64(p))
    clerk_sums = limb_recombine_host(np.asarray(acc), p).T  # (n, B)
    out = reconstruct(jnp.asarray(clerk_sums), [0, 1, 2, 4, 5, 6, 7], scheme, dim)
    got = positive(np.asarray(out), p)
    want = np.array(
        [sum(int(v) for v in secrets[:, j]) % p for j in range(dim)], dtype=np.int64
    )
    np.testing.assert_array_equal(got, want)
    # device-side wide mod-sum agrees with exact host sums
    plain = np.asarray(mod_sum_wide_jnp(jnp.asarray(secrets), p, axis=0))
    np.testing.assert_array_equal(positive(plain, p), want)


def test_sharded_clerk_sums_on_mesh(jax_mods):
    import jax
    import jax.numpy as jnp
    from jax import random

    from sda_tpu.parallel import TpuAggregator, full_training_step, make_mesh, shard_participants

    assert len(jax.devices()) == 8, "conftest must force 8 virtual devices"
    mesh = make_mesh(p_size=4, d_size=2)
    p = PACKED.prime_modulus
    dim = 24  # divisible by k * d_size = 3*2
    P_total = 32
    rng = np.random.default_rng(4)
    secrets = rng.integers(0, p, size=(P_total, dim))

    agg, step = full_training_step(PACKED, dim, mesh)
    sharded = shard_participants(jnp.asarray(secrets), mesh)
    out, plain = step(sharded, random.key(3))
    np.testing.assert_array_equal(
        positive(np.asarray(out), p), positive(np.asarray(plain), p)
    )
    np.testing.assert_array_equal(positive(np.asarray(plain), p), _plain_sum(secrets, p))


def test_all_to_all_clerk_sharded_variant(jax_mods):
    """The transpose-as-all_to_all path: clerk-major resharding must give
    the same clerk sums as the psum path."""
    import jax.numpy as jnp
    from jax import random

    from sda_tpu.parallel import TpuAggregator, make_mesh, shard_participants
    from sda_tpu.parallel.engine import reconstruct

    p = PACKED.prime_modulus
    dim = 24
    rng = np.random.default_rng(6)
    secrets = rng.integers(0, p, size=(16, dim))
    mesh = make_mesh(p_size=4, d_size=1)  # 8 clerks / 4 devices = 2 each
    agg = TpuAggregator(PACKED, dim, mesh=mesh)
    fn = agg.sharded_clerk_sums_all_to_all()
    sums = fn(shard_participants(jnp.asarray(secrets), mesh), random.key(11))
    assert sums.shape == (8, dim // 3)
    out = reconstruct(jnp.asarray(np.asarray(sums)), range(8), PACKED, dim)
    np.testing.assert_array_equal(
        positive(np.asarray(out), p), _plain_sum(secrets, p)
    )


def test_sharded_matches_engine_across_mesh_shapes(jax_mods):
    import jax.numpy as jnp
    from jax import random

    from sda_tpu.parallel import full_training_step, make_mesh, shard_participants

    p = ADDITIVE.modulus
    dim = 16
    rng = np.random.default_rng(5)
    secrets = rng.integers(0, p, size=(8, dim))
    for (ps, ds) in [(8, 1), (2, 4), (1, 8)]:
        mesh = make_mesh(p_size=ps, d_size=ds)
        agg, step = full_training_step(ADDITIVE, dim, mesh)
        out, plain = step(shard_participants(jnp.asarray(secrets), mesh), random.key(9))
        np.testing.assert_array_equal(
            positive(np.asarray(out), p), _plain_sum(secrets, p)
        )


def test_sharded_sum_first_fabric(jax_mods):
    """The sum-first hot loop over the mesh: per-device limb sums + one
    psum must reconstruct to the plaintext sum, and the accumulator's
    verification handle must equal the batched plaintext sums."""
    import jax.numpy as jnp
    from jax import random

    from sda_tpu.parallel import make_mesh, make_plan, shard_participants, sharded_value_limb_sums
    from sda_tpu.parallel.engine import reconstruct
    from sda_tpu.parallel.sumfirst import clerk_sums_from_limb_acc

    p = PACKED.prime_modulus
    dim = 24
    P_total = 32
    rng = np.random.default_rng(12)
    secrets = rng.integers(0, p, size=(P_total, dim))
    for (ps, ds) in [(8, 1), (4, 2)]:
        mesh = make_mesh(p_size=ps, d_size=ds)
        plan = make_plan(PACKED, dim)
        fn = sharded_value_limb_sums(plan, mesh)
        acc = np.asarray(fn(shard_participants(jnp.asarray(secrets), mesh), random.key(7)))
        assert acc.shape == (1, plan.n_batches, plan.input_size + plan.rand_size)
        clerk, vsum = clerk_sums_from_limb_acc(acc, plan)
        out = reconstruct(jnp.asarray(clerk), range(PACKED.share_count), PACKED, dim)
        np.testing.assert_array_equal(positive(np.asarray(out), p), _plain_sum(secrets, p))
        np.testing.assert_array_equal(
            vsum[:, : plan.input_size],
            _plain_sum(secrets, p).reshape(plan.n_batches, plan.input_size),
        )


def test_sharded_sum_first_rejects_nondivisible_dim(jax_mods):
    """dim not divisible by input_size*d_size must be a loud error — each
    d-shard pads its own tail independently, silently corrupting batches."""
    from sda_tpu.parallel import make_mesh, make_plan, sharded_value_limb_sums

    mesh = make_mesh(p_size=4, d_size=2)
    plan = make_plan(PACKED, 26)  # 26 % (3*2) != 0
    with pytest.raises(ValueError, match="divide over input_size"):
        sharded_value_limb_sums(plan, mesh)


def test_sharded_sum_first_wide_modulus(jax_mods):
    """Sum-first on the mesh at 61-bit width: the two-limb exact path
    (no int64 overflow, no mod on device) through the same psum fabric."""
    import jax.numpy as jnp
    from jax import random

    from sda_tpu.ops import find_packed_parameters
    from sda_tpu.parallel import make_mesh, make_plan, shard_participants, sharded_value_limb_sums
    from sda_tpu.parallel.sumfirst import clerk_sums_from_limb_acc, reconstruct_from_clerk_sums

    pw, w2, w3 = find_packed_parameters(3, 4, 8, min_modulus_bits=60, seed=1)
    scheme = PackedShamirSharing(3, 8, 4, pw, w2, w3)
    dim = 12
    P_total = 16
    rng = np.random.default_rng(13)
    secrets = rng.integers(pw - 50_000, pw, size=(P_total, dim)).astype(np.int64)
    mesh = make_mesh(p_size=4, d_size=2)
    plan = make_plan(scheme, dim)
    acc = np.asarray(
        sharded_value_limb_sums(plan, mesh)(
            shard_participants(jnp.asarray(secrets), mesh), random.key(8)
        )
    )
    assert acc.shape[0] == 2  # two base-2^32 limbs at 61 bits
    clerk, vsum = clerk_sums_from_limb_acc(acc, plan)
    out = reconstruct_from_clerk_sums(clerk, range(8), scheme, dim)
    want = np.array(
        [sum(int(v) for v in secrets[:, j]) % pw for j in range(dim)], dtype=np.int64
    )
    np.testing.assert_array_equal(positive(np.asarray(out), pw), want)


def test_basic_shamir_engine_end_to_end():
    """BasicShamir through the TPU engine: secure_sum over a 30-bit prime
    with reconstruction from a dropped-clerk subset."""
    import jax
    import numpy as np

    from sda_tpu.ops.modular import positive
    from sda_tpu.ops.params import is_prime
    from sda_tpu.parallel import TpuAggregator
    from sda_tpu.protocol import BasicShamirSharing

    p = (1 << 30) + 3
    while not is_prime(p):
        p += 2
    scheme = BasicShamirSharing(share_count=6, privacy_threshold=2, prime_modulus=p)
    dim, P = 37, 11
    rng = np.random.default_rng(2)
    secrets = rng.integers(0, p, size=(P, dim))
    agg = TpuAggregator(scheme, dim)
    import jax.numpy as jnp

    out = agg.secure_sum(
        jnp.asarray(secrets), jax.random.key(0), indices=[0, 2, 5]  # 3 of 6 survive
    )
    np.testing.assert_array_equal(
        positive(np.asarray(out), p), secrets.sum(axis=0) % p
    )


def _narrow_plan(dim, k=5, t=2, bits=30):
    from sda_tpu.ops import find_packed_parameters
    from sda_tpu.parallel.engine import make_plan
    from sda_tpu.protocol import PackedShamirSharing

    p, w2, w3 = find_packed_parameters(k, t, 8, min_modulus_bits=bits, seed=0)
    return make_plan(PackedShamirSharing(k, 8, t, p, w2, w3), dim)


@pytest.mark.parametrize("dim,P,k,t", [
    # nb = 5: the dim's pad and one short batch tile; 600 participants = 4
    # lane blocks of 128 and a ragged fifth
    pytest.param(23, 600, 5, 2, id="dim23-P600-ragged-lane-block"),
    # an odd count under one lane block
    pytest.param(23, 37, 5, 2, id="dim23-P37-one-short-block"),
    # nb = 2100: four batch tiles of 512 and a ragged fifth, not a multiple of 128
    pytest.param(10_500, 70, 5, 2, id="dim10500-P70-ragged-batch-tile"),
    # nb = 257, 300 participants: both axes ragged at once
    pytest.param(1_283, 300, 5, 2, id="dim1283-P300-both-ragged"),
    # whole blocks on both axes: no mask, no edge
    pytest.param(2_560, 256, 5, 2, id="dim2560-P256-whole-blocks"),
    # K = 3 value rows: k = 2, t = 1, dim odd
    pytest.param(301, 130, 2, 1, id="K3-dim301-P130"),
])
def test_pallas_participant_path_bit_identical(jax_mods, dim, P, k, t):
    """The fused Pallas participant kernel (interpret mode, passed
    explicitly) produces bit-identical limb accumulators to XLA's named
    formulation (``share_combine_limb_xla``) for the same key, across the pad
    of every axis: a dim that is no multiple of k, batches that fill no batch
    tile and no 128 rows, participants that fill no lane block, K = 7 and
    K < 7. Off the TPU the engine's own entry takes XLA's formulation."""
    import jax.numpy as jnp
    from jax import random

    from sda_tpu.parallel.engine import share_combine_limb, share_combine_limb_xla
    from sda_tpu.parallel.limb_pallas import share_combine_limb_pallas

    plan = _narrow_plan(dim, k, t)
    secrets = jnp.asarray(
        np.random.default_rng(17).integers(0, plan.modulus, size=(P, dim)).astype(np.int32)
    )
    key = random.key(P)
    want = np.asarray(share_combine_limb_xla(secrets, key, plan))
    got = np.asarray(share_combine_limb_pallas(secrets, key, plan, interpret=True))
    np.testing.assert_array_equal(got, want)
    assert want.shape == (5, plan.n_batches, 8) and want.dtype == np.int64 and want.any()
    np.testing.assert_array_equal(np.asarray(share_combine_limb(secrets, key, plan)), want)


@pytest.mark.parametrize("bits,P,dim", [
    # a 32-bit prime: limb extraction needs more than int32
    pytest.param(31, 9, 23, id="p-over-2^31"),
    # 3 900 participants x 35 x 127^2 is past 2^31: the int32 accumulation would wrap
    pytest.param(30, 3_900, 5, id="chunk-past-int32"),
])
def test_what_the_fused_kernel_cannot_hold_takes_the_xla_formulation(jax_mods, bits, P, dim):
    """Past the kernel's bounds the entry takes XLA's formulation without
    raising, whatever the platform (here even lowered for a TPU, with no
    kernel in the text); the kernel's explicit entry refuses by name."""
    import jax
    import jax.numpy as jnp

    from sda_tpu.parallel.engine import share_combine_limb, share_combine_limb_xla
    from sda_tpu.parallel.limb_pallas import fused_fits, share_combine_limb_pallas

    plan = _narrow_plan(dim, bits=bits)
    assert not fused_fits(plan.modulus, P, 7) and fused_fits((1 << 31) - 1, 2_000, 7)
    dtype = jnp.int32 if plan.modulus <= (1 << 31) else jnp.int64
    secrets = jnp.asarray(
        np.random.default_rng(3).integers(0, plan.modulus, size=(P, dim)), dtype=dtype
    )
    key = jax.random.key(5)
    step = jax.jit(lambda s, kk: share_combine_limb(s, kk, plan))
    np.testing.assert_array_equal(
        np.asarray(step(secrets, key)), np.asarray(share_combine_limb_xla(secrets, key, plan))
    )
    for_tpu = step.trace(secrets, key).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" not in for_tpu and "dot_general" in for_tpu
    with pytest.raises(ValueError, match="fused participant path"):
        share_combine_limb_pallas(secrets, key, plan, interpret=True)
