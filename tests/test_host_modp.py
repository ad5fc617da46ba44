"""The host epilogue's mod-p arithmetic in machine integers, bit for bit
against python integers written out here: ``ops.modular.mod_limbs_np`` (the
one exact primitive) and its three callers, ``modmatmul_np``'s wide branch,
``sumfirst.clerk_sums_from_limb_acc`` and ``limbmatmul.limb_recombine_host``,
with ``ops.shamir.reconstruct_clerk_sums_host`` on top. No reference below
calls the function it checks."""

import json
import pathlib

import numpy as np
import pytest

from sda_tpu import telemetry
from sda_tpu.ops import find_packed_parameters
from sda_tpu.ops.modular import (
    WIDE_MAX_MODULUS,
    mod_limbs_np,
    modmatmul_np,
    modmatmul_path,
)

REPO = pathlib.Path(__file__).resolve().parents[1]
INT64_MAX = (1 << 63) - 1


def c5_prime():
    """The modulus of the benchmark's ``c5-w61-d100k``."""
    return find_packed_parameters(5, 2, 8, min_modulus_bits=60, seed=0)[0]


MODULI = {
    "2^31+11": (1 << 31) + 11,
    "c5-w61": c5_prime(),
    "2^61-1": (1 << 61) - 1,
    "below-2^62": (1 << 62) - 57,  # the largest prime under WIDE_MAX_MODULUS
}


def object_matmul(A, B, m):
    """What the object-dtype branch computes: the exact product in python
    integers, then the truncated remainder (the dividend's sign)."""
    exact = np.asarray(A, dtype=object) @ np.asarray(B, dtype=object)
    flat = [int(v) for v in np.asarray(exact, dtype=object).reshape(-1)]
    rems = [-(-v % m) if v < 0 else v % m for v in flat]
    return np.array(rems, dtype=np.int64).reshape(np.shape(exact))


def operands(kind, shape, m, rng):
    if kind == "random":
        return rng.integers(0, m, size=shape, dtype=np.int64)
    if kind == "zeros":
        return np.zeros(shape, dtype=np.int64)
    if kind == "p-1":
        return np.full(shape, m - 1, dtype=np.int64)
    if kind == "signed":
        return rng.integers(-(m - 1), m, size=shape, dtype=np.int64)
    if kind == "unreduced":  # non-negative, up to all an int64 holds
        return rng.integers(0, INT64_MAX, size=shape, dtype=np.int64, endpoint=True)
    raise AssertionError(kind)


@pytest.fixture
def counted():
    """Wide products by path, counted from here."""
    telemetry.reset()

    def read():
        return {
            dict(labels)["path"]: value
            for (name, labels), value in telemetry.get_registry().snapshot()["counters"].items()
            if name == "sda_wide_mod_products_total"
        }

    yield read
    telemetry.reset()


# --- the primitive -----------------------------------------------------------


def limbs_of(value, shift, count):
    return [(value >> (shift * j)) & ((1 << shift) - 1) for j in range(count)]


@pytest.mark.parametrize("name", sorted(MODULI))
def test_mod_limbs_is_exact_where_the_float_quotient_is_one_off(name):
    """Values a step either side of a multiple of p, where floor(V_float /
    p) misses by one: quotients over the whole range in one call (under
    2**49: the check bounds V by the limbs' maxima, each taken alone), and
    the largest the docstring allows one value a call."""
    p = MODULI[name]
    rng = np.random.default_rng(p % 1000)
    steps = (0, 1, p - 1, p // 2)
    shift = -(-(p.bit_length() + 50) // 4)
    quotients = [0, 1, 2, (1 << 49) - 1] + [int(q) for q in rng.integers(0, 1 << 49, size=300)]
    values = [q * p + d for q in quotients for d in steps]
    limbs = [np.array(col, dtype=np.int64) for col in zip(*(limbs_of(v, shift, 4) for v in values))]
    got = mod_limbs_np(limbs, shift, p)
    assert got.dtype == np.int64 and got.tolist() == [v % p for v in values]
    for q in ((1 << 50) - 1, (1 << 50) - (1 << 20), (1 << 49) + 1):
        for d in steps:
            value = q * p + d
            got = mod_limbs_np([np.array([limb]) for limb in limbs_of(value, shift, 4)], shift, p)
            assert got.tolist() == [d], (name, q, d)


@pytest.mark.parametrize("name", sorted(MODULI))
@pytest.mark.parametrize("count,shift", [(1, 0), (2, 32), (3, 21), (4, 11), (4, 32)])
def test_mod_limbs_matches_python_integers_on_random_limbs(name, count, shift):
    """Limbs that overlap and run as high as the bound V < 2**50 p (and
    int64) lets them: each may spell a count-th of it."""
    p = MODULI[name]
    rng = np.random.default_rng(count)
    share = ((p << 50) - 1) // count
    limbs = [
        rng.integers(0, min(INT64_MAX, share >> (shift * j)), size=(40, 9), dtype=np.int64, endpoint=True)
        for j in range(count)
    ]
    exact = sum(np.asarray(t, dtype=object) << (shift * j) for j, t in enumerate(limbs))
    got = mod_limbs_np(limbs, shift, p)
    assert got.shape == (40, 9) and got.dtype == np.int64
    assert np.array_equal(got, (exact % p).astype(np.int64))


@pytest.mark.parametrize("why,limbs,shift,p", [
    ("negative limb", [np.array([3, -1])], 32, MODULI["c5-w61"]),
    ("value of 2**50 p or more", [np.array([0]), np.array([1 << 62])], 60, (1 << 31) + 11),
    ("modulus of 2**62", [np.array([1])], 32, WIDE_MAX_MODULUS),
    ("modulus of 1", [np.array([1])], 32, 1),
    ("five limbs", [np.array([1])] * 5, 8, MODULI["c5-w61"]),
    ("no limb", [], 8, MODULI["c5-w61"]),
    ("shapes differ", [np.array([1, 2]), np.array([1])], 8, MODULI["c5-w61"]),
])
def test_mod_limbs_checks_what_its_exactness_rests_on(why, limbs, shift, p):
    with pytest.raises(ValueError):
        mod_limbs_np(limbs, shift, p)


def test_mod_limbs_takes_scalars_and_empty_arrays():
    p = MODULI["2^61-1"]
    assert int(mod_limbs_np([np.int64(5), np.int64(7)], 61, p)) == (5 + (7 << 61)) % p
    assert mod_limbs_np([np.zeros((0, 3), np.int64)] * 2, 32, p).shape == (0, 3)


# --- modmatmul_np's wide branch ----------------------------------------------

SHAPES = {
    "share-matmul": ((20000, 7), (7, 8)),  # c5-w61-d100k's (B, K) @ (K, n)
    "reconstruct": ((20000, 7), (7, 5)),  # its (B, R) @ (R, k)
    "reshare-column": ((300, 1), (1, 5)),
    "long-K": ((6, 1500), (1500, 3)),  # over the 682 a float64 matmul holds at 61 bits
    "wide-right": ((3, 4), (4, 900)),  # the right operand is the larger one
}


@pytest.mark.parametrize("kind", ["random", "zeros", "p-1", "signed", "unreduced"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("name", sorted(MODULI))
def test_modmatmul_wide_is_the_object_product_bit_for_bit(name, shape, kind, counted):
    m = MODULI[name]
    rng = np.random.default_rng(len(shape) + len(kind))
    A = operands(kind, SHAPES[shape][0], m, rng)
    B = operands(kind, SHAPES[shape][1], m, rng)
    path = modmatmul_path(A, B, m)
    got = modmatmul_np(A, B, m)
    want = object_matmul(A, B, m)
    assert got.dtype == np.int64 and got.shape == want.shape
    assert np.array_equal(got, want)
    # negative entries alone go the old road; the counter says which was taken
    assert path == ("object" if kind == "signed" else "limb")
    assert counted() == {path: 1}


@pytest.mark.parametrize("name", sorted(MODULI))
def test_modmatmul_wide_mixes_one_signed_operand_with_a_canonical_one(name):
    m = MODULI[name]
    rng = np.random.default_rng(5)
    A = operands("signed", (500, 7), m, rng)
    B = operands("random", (7, 8), m, rng)
    assert np.array_equal(modmatmul_np(A, B, m), object_matmul(A, B, m))
    assert np.array_equal(modmatmul_np(B.T, A.T, m), object_matmul(B.T, A.T, m))


@pytest.mark.parametrize("what,A,B", [
    ("stacked left operand", np.arange(2 * 3 * 4 * 7).reshape(2, 3, 4, 7) * (1 << 50), np.arange(7 * 2).reshape(7, 2) + (1 << 60)),
    ("vector left operand", np.arange(7) + (1 << 59), np.arange(7 * 3).reshape(7, 3) + (1 << 60)),
    ("python integers beyond int64", np.array([[1 << 70, 3]], dtype=object), np.array([[5], [1 << 64]], dtype=object)),
    ("object dtype that fits", np.array([[1 << 60, 3]], dtype=object), np.array([[5], [1 << 61]], dtype=object)),
    ("no rows", np.zeros((0, 7), np.int64), np.ones((7, 8), np.int64)),
    ("no contraction", np.zeros((4, 0), np.int64), np.ones((0, 8), np.int64)),
])
def test_modmatmul_wide_keeps_every_input_the_object_branch_took(what, A, B):
    m = MODULI["c5-w61"]
    got = modmatmul_np(A, B, m)
    want = object_matmul(A, B, m)
    assert got.dtype == np.int64 and got.shape == want.shape, what
    assert np.array_equal(got, want), what


def test_modmatmul_of_2_to_62_and_beyond_stays_on_python_integers(counted):
    m = WIDE_MAX_MODULUS + 135
    rng = np.random.default_rng(0)
    A = rng.integers(0, m, size=(50, 7), dtype=np.int64)
    B = rng.integers(0, m, size=(7, 8), dtype=np.int64)
    assert modmatmul_path(A, B, m) == "object"
    assert np.array_equal(modmatmul_np(A, B, m), object_matmul(A, B, m))
    assert counted() == {"object": 1}


def test_modmatmul_below_2_to_31_counts_no_wide_product(counted):
    m = (1 << 31) - 1
    A = np.arange(21, dtype=np.int64).reshape(3, 7) * 99999989 % m
    B = np.arange(56, dtype=np.int64).reshape(7, 8) * 99999971 % m
    assert modmatmul_path(A, B, m) == "int64"
    assert np.array_equal(modmatmul_np(A, B, m), object_matmul(A, B, m))
    assert counted() == {}


# --- the three fabric callers ------------------------------------------------


def packed_plan(dim, bits, seed, k=5, t=2, n=8):
    from sda_tpu.parallel.engine import make_plan
    from sda_tpu.protocol import PackedShamirSharing

    p, w2, w3 = find_packed_parameters(k, t, n, min_modulus_bits=bits, seed=seed)
    scheme = PackedShamirSharing(k, n, t, p, w2, w3)
    return scheme, make_plan(scheme, dim)


def plan_of(config):
    """The plan of a benchmark configuration, at its own modulus and dim."""
    stated = json.loads((REPO / "benchmark" / "configs" / f"{config}.json").read_text())
    s = stated["scheme"]
    return packed_plan(
        stated["dim"], s["min_modulus_bits"], s["parameter_seed"],
        s["secret_count"], s["privacy_threshold"], s["share_count"],
    )


def small_plan(bits, dim=103):
    return packed_plan(dim, bits, seed=1)


def object_epilogue(acc, plan):
    """``clerk_sums_from_limb_acc`` in python integers."""
    p = plan.modulus
    exact = sum(np.asarray(acc[w], dtype=object) << (32 * w) for w in range(acc.shape[0]))
    vsum = exact % p
    clerk = (vsum @ np.asarray(plan.share_matrix.T, dtype=object)) % p
    return clerk.T.astype(np.int64), vsum.astype(np.int64)


def accumulator(kind, limbs, plan, rng):
    shape = (limbs, -(-plan.dim // plan.input_size), plan.input_size + plan.rand_size)
    if kind == "random":  # what 2**31 participants' limb sums can reach
        return rng.integers(0, INT64_MAX, size=shape, dtype=np.int64, endpoint=True)
    if kind == "bound":  # every limb sum at MAX_PARTICIPANTS * (2**32 - 1), capped where int64 ends
        return np.full(shape, INT64_MAX, dtype=np.int64)
    if kind == "zeros":
        return np.zeros(shape, dtype=np.int64)
    if kind == "few":  # a few participants: limb sums of some 2**34
        return rng.integers(0, 1 << 34, size=shape, dtype=np.int64)
    raise AssertionError(kind)


@pytest.mark.parametrize("kind", ["random", "bound", "zeros", "few"])
@pytest.mark.parametrize("bits", [30, 31, 60, 61])
def test_the_sumfirst_epilogue_is_the_python_integer_epilogue(bits, kind):
    from sda_tpu.parallel import sumfirst

    _, plan = small_plan(bits)
    limbs = sumfirst.limb_count_sum(plan.modulus)
    acc = accumulator(kind, limbs, plan, np.random.default_rng(bits))
    got = sumfirst.clerk_sums_from_limb_acc(acc, plan)
    want = object_epilogue(acc, plan)
    for g, w in zip(got, want):
        assert g.dtype == np.int64 and g.shape == w.shape and np.array_equal(g, w)


@pytest.mark.parametrize("config,path", [("c5-w61-d100k", "limb"), ("c4-w31-d50k", "int64")])
def test_a_benchmark_configurations_epilogue_never_falls_back_to_python_integers(config, path, counted):
    """At the configuration's own modulus and dim: the whole host epilogue
    and the reconstruct, bit for bit, and by the counter and the spans'
    ``path`` no wide product of it on the object road."""
    from sda_tpu.ops.shamir import reconstruct_clerk_sums_host, reconstruction_matrix
    from sda_tpu.parallel import sumfirst

    scheme, plan = plan_of(config)
    assert "object" not in counted()  # the plan's share matrix
    p = plan.modulus
    acc = accumulator("random", sumfirst.limb_count_sum(p), plan, np.random.default_rng(1))
    telemetry.reset()
    clerk_sums, vsum = sumfirst.clerk_sums_from_limb_acc(acc, plan)
    survivors = [1, 2, 3, 4, 5, 6, 7]
    aggregate = reconstruct_clerk_sums_host(clerk_sums, survivors, scheme, plan.dim)
    spans = telemetry.spans(name="fabric.")
    assert [s["name"] for s in spans] == [
        "fabric.epilogue.recombine", "fabric.epilogue.share_matmul", "fabric.reconstruct",
    ]
    assert [s["attrs"]["path"] for s in spans] == [path] * 3
    assert counted() == ({"limb": 3} if path == "limb" else {})

    want_clerk, want_vsum = object_epilogue(acc, plan)
    assert np.array_equal(clerk_sums, want_clerk) and np.array_equal(vsum, want_vsum)
    L = np.asarray(reconstruction_matrix(scheme, survivors), dtype=object)  # (k, R)
    want = (np.asarray(want_clerk[survivors].T, dtype=object) @ L.T) % p
    assert aggregate.dtype == np.int64
    assert np.array_equal(aggregate, want.astype(np.int64).reshape(-1)[: plan.dim])
    # and what was reconstructed is what went in: the secrets' columns of the value sums
    assert np.array_equal(aggregate, want_vsum[:, : plan.input_size].reshape(-1)[: plan.dim])


def object_recombine(partials, p):
    out = np.zeros(np.shape(partials)[1:], dtype=object)
    for w in range(np.shape(partials)[0]):
        out = (out + np.asarray(partials[w], dtype=object) * pow(128, w, p)) % p
    return out.astype(np.int64)


@pytest.mark.parametrize("kind,dtype,high", [
    ("one chunk's int32 partials", np.int32, (1 << 31) - 1),
    ("an accumulator of many chunks", np.int64, 1 << 50),
    ("all an int64 holds", np.int64, INT64_MAX),
    ("zeros", np.int64, 0),
])
@pytest.mark.parametrize("bits", [30, 31, 60, 61])
def test_limb_recombine_host_is_the_python_integer_recombine(bits, kind, dtype, high, counted):
    from sda_tpu.parallel.limbmatmul import limb_count, limb_recombine_host

    p = small_plan(bits)[1].modulus
    rng = np.random.default_rng(bits)
    partials = rng.integers(0, high, size=(limb_count(p), 23, 8), dtype=dtype, endpoint=True)
    telemetry.reset()
    got = limb_recombine_host(partials, p)
    assert got.dtype == np.int64 and got.shape == (23, 8)
    assert np.array_equal(got, object_recombine(partials, p))
    (span,) = telemetry.spans(name="fabric.epilogue.recombine")
    assert span["attrs"]["path"] == ("int64" if bits == 30 else "limb")
    assert counted() == ({} if bits == 30 else {"limb": 1})


@pytest.mark.parametrize("bits", [30, 61])
def test_limb_recombine_host_canonicalises_negative_partials_as_python_does(bits):
    from sda_tpu.parallel.limbmatmul import limb_count, limb_recombine_host

    p = small_plan(bits)[1].modulus
    rng = np.random.default_rng(3)
    partials = rng.integers(-(1 << 40), 1 << 40, size=(limb_count(p), 11, 8), dtype=np.int64)
    assert np.array_equal(limb_recombine_host(partials, p), object_recombine(partials, p))
