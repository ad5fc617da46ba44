"""Field-math core tests: Rust-% semantics, params, packed Shamir, ChaCha."""

import numpy as np
import pytest

from sda_tpu.ops import (
    element_order,
    find_packed_parameters,
    is_prime,
    validate_packed_parameters,
)
from sda_tpu.ops.lagrange import lagrange_matrix
from sda_tpu.ops.modular import (
    modmatmul_np,
    positive,
    rust_rem_int,
    rust_rem_np,
)
from sda_tpu.ops.ntt import intt, ntt
from sda_tpu.ops.rng import uniform_mod_host
from sda_tpu.ops import chacha, shamir
from sda_tpu.protocol import PackedShamirSharing

# the verified reference test vector (full_loop.rs:56-64)
REF_SCHEME = PackedShamirSharing(
    secret_count=3,
    share_count=8,
    privacy_threshold=4,
    prime_modulus=433,
    omega_secrets=354,
    omega_shares=150,
)


def test_rust_rem_semantics():
    # Rust % truncates toward zero: -7 % 5 == -2
    assert rust_rem_int(-7, 5) == -2
    assert rust_rem_int(7, 5) == 2
    assert rust_rem_int(-10, 5) == 0
    xs = np.array([-7, 7, -10, 0, 12, -12], dtype=np.int64)
    np.testing.assert_array_equal(rust_rem_np(xs, 5), [-2, 2, 0, 0, 2, -2])
    np.testing.assert_array_equal(positive(rust_rem_np(xs, 5), 5), [3, 2, 0, 0, 2, 3])


def test_rust_rem_jax_matches_numpy():
    import jax.numpy as jnp

    from sda_tpu.ops.modular import mod_sum_jnp, rust_rem

    xs = np.array([-7, 7, -10, 0, 12, -12], dtype=np.int32)
    got = np.asarray(rust_rem(jnp.asarray(xs), 5))
    np.testing.assert_array_equal(got, rust_rem_np(xs, 5))

    mat = np.array([[-3, 4], [2, -4], [1, 1]], dtype=np.int32)
    got = np.asarray(mod_sum_jnp(jnp.asarray(mat), 5, axis=0))
    np.testing.assert_array_equal(got, rust_rem_np(mat.astype(np.int64).sum(0), 5))


def test_prime_and_orders_of_reference_vector():
    assert is_prime(433)
    assert element_order(354, 433) == 8  # = secret_count + threshold + 1 = 2^3
    assert element_order(150, 433) == 9  # = share_count + 1 = 3^2
    validate_packed_parameters(REF_SCHEME)


def test_find_packed_parameters():
    p, w2, w3 = find_packed_parameters(
        secret_count=3, privacy_threshold=4, share_count=8, min_modulus_bits=8, seed=0
    )
    scheme = PackedShamirSharing(3, 8, 4, p, w2, w3)
    validate_packed_parameters(scheme)

    # a bigger config: k=64, t=63, n=242 -> m2=128, m3=243
    p, w2, w3 = find_packed_parameters(64, 63, 242, min_modulus_bits=26, seed=0)
    assert p > 2**26
    validate_packed_parameters(PackedShamirSharing(64, 242, 63, p, w2, w3))


def test_ntt_roundtrip_and_lagrange():
    p = 433
    rng = np.random.default_rng(0)
    vals = rng.integers(0, p, size=(5, 8)).astype(np.int64)
    coeffs = intt(vals, 354, p)
    back = ntt(coeffs, 354, p)
    np.testing.assert_array_equal(positive(back, p), positive(vals, p))

    # lagrange: interpolate a known polynomial from 4 points, evaluate elsewhere
    poly = [7, 3, 0, 5]  # 7 + 3x + 5x^3

    def ev(x):
        return sum(c * pow(x, i, p) for i, c in enumerate(poly)) % p

    xs = [2, 5, 11, 17]
    targets = [1, 23, 100]
    L = lagrange_matrix(xs, targets, p)
    ys = np.array([ev(x) for x in xs], dtype=np.int64)
    got = positive(modmatmul_np(ys[None, :], L.T, p)[0], p)
    np.testing.assert_array_equal(got, [ev(t) for t in targets])


def share_once(scheme, secrets, rng):
    S = shamir.share_matrix(scheme)
    t = scheme.privacy_threshold
    randomness = rng.integers(0, scheme.prime_modulus, size=(1, t)).astype(np.int64)
    return shamir.share_batches(np.asarray([secrets], dtype=np.int64), randomness, S, scheme.prime_modulus)[0]


@pytest.mark.parametrize("scheme", [REF_SCHEME])
def test_packed_shamir_share_reconstruct(scheme):
    p = scheme.prime_modulus
    rng = np.random.default_rng(1)
    secrets = np.array([5, 100, 432], dtype=np.int64)
    shares = share_once(scheme, secrets, rng)
    assert shares.shape == (scheme.share_count,)

    R = shamir.reconstruct_limit(scheme)
    # every size-R subset reconstructs exactly
    import itertools

    for indices in itertools.combinations(range(scheme.share_count), R):
        L = shamir.reconstruction_matrix(scheme, list(indices))
        got = shamir.reconstruct_batches(shares[None, list(indices)], L, p)[0]
        np.testing.assert_array_equal(positive(got, p), secrets)


def test_packed_shamir_linearity():
    """Sum of sharings reconstructs to the sum of secrets — the core MPC
    property that makes clerk-side summation an aggregation."""
    scheme = REF_SCHEME
    p = scheme.prime_modulus
    rng = np.random.default_rng(2)
    s1 = np.array([1, 2, 3], dtype=np.int64)
    s2 = np.array([10, 20, 30], dtype=np.int64)
    shares = rust_rem_np(share_once(scheme, s1, rng) + share_once(scheme, s2, rng), p)
    indices = [0, 2, 3, 4, 5, 6, 7]  # clerk 1 dropped out
    assert len(indices) >= shamir.reconstruct_limit(scheme)
    L = shamir.reconstruction_matrix(scheme, indices)
    got = shamir.reconstruct_batches(shares[None, indices], L, p)[0]
    np.testing.assert_array_equal(positive(got, p), (s1 + s2) % p)


def test_packed_shamir_privacy_shape():
    """Any t shares alone are uniform-ish: check they change when only
    randomness changes (secrets fixed) — a smoke test, not a proof."""
    scheme = REF_SCHEME
    rng = np.random.default_rng(3)
    secrets = np.array([7, 7, 7], dtype=np.int64)
    a = share_once(scheme, secrets, rng)
    b = share_once(scheme, secrets, rng)
    assert not np.array_equal(a, b)


def test_uniform_mod_host_unbiased_range():
    draws = uniform_mod_host((10000,), 433)
    assert draws.min() >= 0 and draws.max() < 433
    # crude uniformity: all residues hit for 10k draws over 433 buckets
    assert len(np.unique(draws)) == 433


def test_chacha_block_known_vector():
    """djb ChaCha20, zero key, zero nonce, counter 0 — canonical keystream."""
    words = chacha.chacha_blocks(np.zeros(8, dtype=np.uint32), 0, 1)[0]
    stream = words.astype("<u4").tobytes()
    assert stream[:32].hex() == (
        "76b8e0ada0f13d90405d6ae55386bd28bdd219b8a08ded1aa836efcc8b770dc7"
    )


def test_chacha_device_bit_identical_to_host():
    """The TPU-kernel obligation (SURVEY.md §2): mask expansion on device
    must be bit-identical to the host expansion, or unmasking silently
    corrupts results."""
    import jax.numpy as jnp

    for seed in ([1, 2, 3, 4], [0xFFFFFFFF, 7], list(range(8))):
        seed_np = np.array(seed, dtype=np.uint32)
        for dim, m in [(1, 433), (100, 433), (1000, (1 << 31) - 1), (257, 2**61 - 1)]:
            host = chacha.expand_seed(seed_np, dim, m)
            dev = np.asarray(chacha.expand_seed_jnp(jnp.asarray(seed_np), dim, m))
            np.testing.assert_array_equal(dev, host, err_msg=f"dim={dim} m={m}")
    # raw block function parity
    blocks_host = chacha.chacha_blocks(np.arange(8, dtype=np.uint32), 5, 4)
    blocks_dev = np.asarray(chacha.chacha_blocks_jnp(jnp.arange(8, dtype=jnp.uint32), 5, 4))
    np.testing.assert_array_equal(blocks_dev, blocks_host)


def test_chacha_expand_deterministic_and_in_range():
    seed = np.array([1, 2, 3, 4], dtype=np.uint32)
    a = chacha.expand_seed(seed, 1000, 433)
    b = chacha.expand_seed(seed, 1000, 433)
    np.testing.assert_array_equal(a, b)
    assert a.min() >= 0 and a.max() < 433
    c = chacha.expand_seed(np.array([1, 2, 3, 5], dtype=np.uint32), 1000, 433)
    assert not np.array_equal(a, c)
    # prefix-stability: expanding to a longer dim keeps the prefix
    d = chacha.expand_seed(seed, 2000, 433)
    np.testing.assert_array_equal(d[:1000], a)


def test_chacha_pallas_kernel_bit_identical():
    """The Pallas TPU kernel (ops/chacha_pallas.py) must produce the same
    keystream bits as the numpy host path — run here on the interpreter
    (CPU test mesh); chip_smoke.py holds the compiled kernel to the same
    bits on the TPU."""
    import jax.numpy as jnp

    from sda_tpu.ops import chacha_pallas

    for seed, first, n in [(np.arange(8), 0, 1), (np.array([1, 2]), 5, 700)]:
        host = chacha.chacha_blocks(seed.astype(np.uint32), first, n)
        dev = np.asarray(
            chacha_pallas.chacha_blocks_pallas(
                jnp.asarray(seed, dtype=jnp.uint32), first, n, interpret=True
            )
        )
        np.testing.assert_array_equal(dev, host, err_msg=f"first={first} n={n}")


def test_chacha_batch_expand_matches_per_seed_host():
    """expand_seeds_batch row p == expand_seed(seed_p) bit-for-bit, and
    combine_masks_device == the host unmasker's sum — across modulus tiers
    (rejection and non-rejection zones) and both round backends."""
    import jax.numpy as jnp

    from sda_tpu.ops import chacha_pallas

    rng = np.random.default_rng(7)
    seeds = rng.integers(0, 2**32, size=(5, 4), dtype=np.uint64).astype(np.uint32)
    for dim, m in [(64, 433), (100, (1 << 31) - 1), (33, 2**61 - 1), (16, 1 << 32)]:
        want = np.stack([chacha.expand_seed(s, dim, m) for s in seeds])
        for backend in ("jnp", "interpret"):  # jnp rounds / pallas interpreter
            got = np.asarray(
                chacha_pallas.expand_seeds_batch(
                    jnp.asarray(seeds), dim, m, backend=backend
                )
            )
            np.testing.assert_array_equal(got, want, err_msg=f"m={m} b={backend}")
        combined = np.asarray(
            chacha_pallas.combine_masks_device(jnp.asarray(seeds), dim, m, chunk=2)
        )
        np.testing.assert_array_equal(combined, want.sum(axis=0) % m, err_msg=f"m={m}")


def test_chacha_masker_device_dispatch_matches_host(monkeypatch):
    """ChaChaMasker.combine above the device threshold must agree with the
    host loop bit-for-bit (the silent-corruption hazard of SURVEY hard part
    #4 — dispatch may change throughput, never results)."""
    from sda_tpu.crypto import masking as masking_mod
    from sda_tpu.crypto.masking import ChaChaMasker

    dim, m = 257, (1 << 31) - 1
    masker = ChaChaMasker(m, dim, 128)
    rng = np.random.default_rng(11)
    seeds = [rng.integers(0, 2**32, size=4, dtype=np.uint64).astype(np.int64) for _ in range(6)]
    want = masker.combine(seeds)  # below threshold: host loop
    monkeypatch.setattr(ChaChaMasker, "DEVICE_COMBINE_THRESHOLD", 1)

    # prove the device path is actually taken: a host-loop fallback would
    # call expand_seed and fail loudly instead of passing vacuously
    def _boom(*a, **k):
        raise AssertionError("fell back to host loop")

    monkeypatch.setattr(masking_mod, "expand_seed", _boom)
    got = masker.combine(seeds)  # device path (jnp rounds on CPU mesh)
    np.testing.assert_array_equal(got, want)


def test_chacha_batch_expand_high_rejection_modulus():
    """Regression: a prime just above a power of two rejects ~12.5% of u64
    draws; the batched window must scale with the rejection rate (a fixed
    slack silently corrupts — the masks would disagree with the host
    expansion participants used to mask)."""
    import jax.numpy as jnp

    from sda_tpu.ops import chacha_pallas

    m = 2305843009213693967  # smallest prime > 2^61 -> q ~ 12.5%
    dim = 2000  # ~285 expected rejections >> any fixed slack
    seeds = np.arange(8, dtype=np.uint32).reshape(2, 4)
    want = np.stack([chacha.expand_seed(s, dim, m) for s in seeds])
    got = np.asarray(chacha_pallas.expand_seeds_batch(jnp.asarray(seeds), dim, m))
    np.testing.assert_array_equal(got, want)


#: the masked cell's prime (benchmark/configs/c5-w61-d100k-chacha.json): 1/16 of the draws rejected
CELL_PRIME = int(find_packed_parameters(5, 2, 8, min_modulus_bits=60, seed=0)[0])

#: (modulus, dim, rows, pairs, lanes): ``pairs`` cuts the keystream window by
#: hand (None: ``_window_pairs``'); a window is whole blocks, eight pairs each.
#: ``lanes`` is the kernel's loop step (None: the module's, one step a stage
#: at these sizes): a vreg's width makes a stage walk a row in several steps,
#: in place, as it does at a deployment's width
COMPACTION_CASES = {
    "cell-prime-7-rows": (CELL_PRIME, 300, 7, None, 128),
    "cell-prime-9-rows": (CELL_PRIME, 400, 9, None, 256),
    "cell-prime-1-row": (CELL_PRIME, 200, 1, None, None),
    "31-bit-prime-dim-not-a-lane-multiple": ((1 << 31) - 1, 257, 8, None, 128),
    "31-bit-prime-dim-under-128": ((1 << 31) - 1, 100, 7, 160, None),
    "power-of-two-dim-under-128": (1 << 32, 127, 8, 168, 128),
    "2^63-half-rejected": (1 << 63, 130, 9, None, 128),
    "2^63-dim-under-128-1-row": (1 << 63, 100, 1, None, None),
    "max-shift-a-power-of-two": (CELL_PRIME, 192, 8, 256, 128),
    "max-shift-one-less": (CELL_PRIME, 193, 9, 256, None),
    "short-rows-window-cut": (CELL_PRIME, 300, 9, 312, 128),
    "short-rows-half-rejected": (1 << 63, 150, 7, 256, None),
    "window-under-a-lane-tile-takes-the-twin": (CELL_PRIME, 64, 8, None, None),
}


def compaction_ticks(path):
    from sda_tpu import telemetry

    return sum(
        c["value"] for c in telemetry.snapshot()["counters"]
        if c["name"] == "sda_crypto_chacha_compactions_total" and c["labels"].get("path") == path
    )


@pytest.mark.parametrize("case", COMPACTION_CASES)
def test_compaction_kernel_is_the_stages_bit_for_bit(case, monkeypatch):
    """The kernel ``chacha_compact`` (its source, on the interpreter), which
    takes the ``(2, P, window)`` word planes and the zone and makes the zone
    test and the counts itself, against ``_first_accepted`` and the plain
    definition, on a real keystream with a row of zero words (every draw
    accepted) and one of ``0xFFFFFFFF`` words (every draw rejected at these
    moduli) put in by hand; then the whole expansion by ``interpret`` against
    ``jnp`` and the host's ``expand_seed``, masks and counts, rows that come
    short among them (their kept draws intact, zeros after); and the counter
    says which path ran."""
    import jax.numpy as jnp

    from sda_tpu.ops import chacha_pallas as cp

    modulus, dim, rows, pairs, lanes = COMPACTION_CASES[case]
    if pairs is not None:
        monkeypatch.setattr(cp, "_window_pairs", lambda dim, modulus: pairs)
    if lanes is not None:
        monkeypatch.setattr(cp, "_COMPACT_LANES", lanes)
    n_blocks = (cp._window_pairs(dim, modulus) * 2 + 15) // 16
    window, zone = n_blocks * 8, chacha.rand03_zone(modulus)
    rng = np.random.default_rng(sorted(COMPACTION_CASES).index(case))
    seeds = rng.integers(0, 1 << 32, size=(rows, 4), dtype=np.uint64).astype(np.uint32)
    stream = np.stack([chacha.chacha_blocks(s, 0, n_blocks).reshape(-1) for s in seeds])

    def draws(words):
        hi, lo = words[:, 0::2], words[:, 1::2]
        return hi, lo, ((hi.astype(np.uint64) << np.uint64(32)) | lo) < np.uint64(zone)

    words = stream.copy()
    words[0] = 0  # a row with no rejected draw
    if rows > 2:
        words[2] = 0xFFFFFFFF  # and one with no draw at all
    hi, lo, ok = draws(words)
    assert ok[0].all() and (rows <= 2 or not ok[2].any())

    # the kernel alone: at any shape, whatever the dispatcher would choose
    want_hi, want_lo = np.zeros((2, rows, dim), np.uint32)
    for r in range(rows):
        kept = np.flatnonzero(ok[r])
        before = kept - np.arange(len(kept))  # rejected draws before each
        kept = kept[before <= window - dim][:dim]
        want_hi[r, : len(kept)], want_lo[r, : len(kept)] = hi[r, kept], lo[r, kept]
    twin = cp._first_accepted(jnp.asarray(hi), jnp.asarray(lo), jnp.asarray(ok), dim)
    kernel = cp._compact_pallas(jnp.asarray(np.stack([hi, lo])), dim, zone, interpret=True)
    for got in (twin, kernel):
        assert got[0].shape == (rows, dim) and got[0].dtype == jnp.uint32
        assert np.array_equal(got[0], want_hi) and np.array_equal(got[1], want_lo)
    assert kernel[2].shape == (rows,) and kernel[2].dtype == jnp.int32
    assert np.array_equal(kernel[2], ok.sum(axis=1))
    _hi, _lo, ok = draws(stream)
    counts = ok.sum(axis=1)

    # the whole expansion, by the path the shape takes
    path = "interpret" if cp._compact_fits(window, dim) else "jnp"
    assert (path == "jnp") == ("takes-the-twin" in case)
    other = "interpret" if path == "jnp" else "jnp"
    before = compaction_ticks(path), compaction_ticks(other)
    masks, got_counts = cp.expand_seeds_counts(jnp.asarray(seeds), dim, modulus, "interpret")
    assert compaction_ticks(path) == before[0] + rows
    assert compaction_ticks(other) == before[1]  # the path ran, and no other
    twin_masks, twin_counts = cp.expand_seeds_counts(jnp.asarray(seeds), dim, modulus, "jnp")
    assert np.array_equal(masks, twin_masks) and masks.dtype == jnp.int64
    assert np.array_equal(got_counts, counts) and np.array_equal(twin_counts, counts)
    assert (int(counts.min()) < dim) == ("short-rows" in case), counts
    for r in range(rows):
        host = chacha.expand_seed(seeds[r], dim, modulus)
        if counts[r] >= dim:
            assert np.array_equal(masks[r], host), r
            continue
        # draws pushed out of a short row are let go: those kept are a prefix
        kept = int(np.count_nonzero(np.flatnonzero(ok[r]) - np.arange(counts[r]) <= window - dim))
        assert np.array_equal(masks[r, :kept], host[:kept]) and not np.any(masks[r, kept:]), r


def test_verify_scheme_accepts_valid_and_rejects_degenerate(monkeypatch):
    """verify_scheme proves t-privacy (every t-subset of share rows fully
    randomized) and universal reconstruction for real schemes, and flags a
    doctored share matrix whose randomness block is rank-deficient."""
    from sda_tpu.ops import shamir as shamir_mod
    from sda_tpu.ops.shamir import verify_scheme
    from sda_tpu.protocol import BasicShamirSharing

    # reference-verified packed vector + generated params + basic
    verify_scheme(PackedShamirSharing(3, 8, 4, 433, 354, 150))
    p, w2, w3 = find_packed_parameters(5, 2, 8, min_modulus_bits=30, seed=0)
    verify_scheme(PackedShamirSharing(5, 8, 2, p, w2, w3))
    verify_scheme(BasicShamirSharing(share_count=6, privacy_threshold=3, prime_modulus=433))

    # doctored: zero out one share row's randomness block -> that "clerk"
    # sees a deterministic function of the secrets
    scheme = BasicShamirSharing(share_count=4, privacy_threshold=2, prime_modulus=433)
    good = shamir_mod.share_matrix(scheme)
    bad = good.copy()
    bad[1, 1:] = 0
    monkeypatch.setattr(shamir_mod, "share_matrix", lambda s: bad)
    with pytest.raises(ValueError, match="t-privacy violated"):
        verify_scheme(scheme)


def test_chacha_expand_matches_rand03_transcription():
    """expand_seed must be bit-exact to the reference's mask expansion:
    rand-0.3 ``ChaChaRng::from_seed(&seed)`` + ``gen_range(0_i64, m)``
    per element (client/src/crypto/masking/chacha.rs:36-39,56-77;
    client/Cargo.toml pins rand "0.3").

    The oracle below is an independent scalar transcription of rand
    0.3's algorithm — ChaChaRng (chacha.rs: 16-word buffer in output
    order, 128-bit counter over words 12..16), the Rng trait's default
    ``next_u64`` (high u32 first), and ``gen_range``'s zone rejection
    (distributions/range.rs integer_impl!: zone = MAX - MAX % range,
    accept strictly below) — sharing no code with the vectorized
    implementation. Moduli cover: the reference's own 433, primes, a
    power of two (where the rand zone rejects the top m values even
    though 2^64 % m == 0 — the case a textbook zone silently gets
    wrong), and a ~1/3-rejection modulus stressing the refill loop."""
    M32 = 0xFFFFFFFF

    def rand03_expand(seed_words, dim, m):
        base = [0x61707865, 0x3320646E, 0x79622D32, 0x6B206574] + [0] * 12
        for i, w in enumerate(list(seed_words)[:8]):
            base[4 + i] = int(w) & M32

        def quarter(x, a, b, c, d):
            x[a] = (x[a] + x[b]) & M32
            x[d] ^= x[a]
            x[d] = ((x[d] << 16) | (x[d] >> 16)) & M32
            x[c] = (x[c] + x[d]) & M32
            x[b] ^= x[c]
            x[b] = ((x[b] << 12) | (x[b] >> 20)) & M32
            x[a] = (x[a] + x[b]) & M32
            x[d] ^= x[a]
            x[d] = ((x[d] << 8) | (x[d] >> 24)) & M32
            x[c] = (x[c] + x[d]) & M32
            x[b] ^= x[c]
            x[b] = ((x[b] << 7) | (x[b] >> 25)) & M32

        def u32_stream():
            counter = [0, 0, 0, 0]
            while True:
                inp = base[:12] + counter
                w = list(inp)
                for _ in range(10):
                    quarter(w, 0, 4, 8, 12)
                    quarter(w, 1, 5, 9, 13)
                    quarter(w, 2, 6, 10, 14)
                    quarter(w, 3, 7, 11, 15)
                    quarter(w, 0, 5, 10, 15)
                    quarter(w, 1, 6, 11, 12)
                    quarter(w, 2, 7, 8, 13)
                    quarter(w, 3, 4, 9, 14)
                yield from ((w[i] + inp[i]) & M32 for i in range(16))
                for j in range(4):  # rand 0.3's 128-bit counter
                    counter[j] = (counter[j] + 1) & M32
                    if counter[j]:
                        break

        words = u32_stream()
        u64_max = (1 << 64) - 1
        zone = u64_max - u64_max % m
        out = []
        while len(out) < dim:
            v = (next(words) << 32) | next(words)  # next_u64: high half first
            if v < zone:
                out.append(v % m)
        return out

    rng = np.random.default_rng(11)
    for m in (
        433,  # the reference's full_loop modulus
        (1 << 31) - 1,
        1152921504606846883,  # 60-bit prime
        1 << 32,  # power of two: rand rejects [2^64 - 2^32, 2^64)
        256,
        ((1 << 64) // 3) | 1,  # ~33% rejection: stresses the refill loop
    ):
        for seed_len in (4, 8):
            seed = rng.integers(0, 2**32, size=seed_len, dtype=np.uint32)
            want = rand03_expand(seed, 300, m)
            np.testing.assert_array_equal(
                chacha.expand_seed(seed, 300, m),
                np.array(want, dtype=np.int64),
                err_msg=f"modulus {m}",
            )


def test_chacha_expand_rejects_oversized_modulus():
    """Above 2^63 the reduced draws would wrap negative in the int64 mask
    — raise instead of silently corrupting the aggregate."""
    with pytest.raises(ValueError, match="int64"):
        chacha.expand_seed(np.arange(4, dtype=np.uint32), 8, 2**64 - 59)
    with pytest.raises(ValueError, match="int64"):
        chacha.rand03_zone((1 << 63) + 1)
    assert chacha.rand03_zone(1 << 63) == 1 << 63  # boundary is legal


def test_uniform_mod_host_drbg_path(monkeypatch):
    """Large default-entropy draws route through the native ChaCha DRBG
    (fresh full 256-bit key per call); contract pinned: int64, unbiased
    range, distinct across calls, the gate ACTUALLY takes the DRBG path
    (recorded via monkeypatch, so a gate regression cannot pass silently
    through the urandom fallback), and a custom entropy source always
    takes the deterministic direct path regardless of size."""
    from sda_tpu import native

    for m in (433, 1 << 32, (1 << 61) - 1):
        a = uniform_mod_host((4096,), m)
        b = uniform_mod_host((4096,), m)
        assert a.dtype == np.int64 and a.min() >= 0 and a.max() < m
        assert not np.array_equal(a, b)  # fresh seed per call
    if native.available():
        calls = []
        real = native.chacha_expand

        def recording(seed, dim, modulus):
            calls.append((np.asarray(seed).size, dim))
            return real(seed, dim, modulus)

        monkeypatch.setattr(native, "chacha_expand", recording)
        draws = uniform_mod_host((10000,), 433)
        # the gate took the DRBG path, with the full 8-word (256-bit) key
        assert calls == [(8, 10000)], calls
        # residue coverage on the DRBG path (mirrors the urandom test)
        assert len(np.unique(draws)) == 433
        calls.clear()
        uniform_mod_host((8,), 433)  # small: direct path
        assert calls == []
    det = uniform_mod_host((4096,), 433, entropy=lambda k: b"\x2a" * k)
    assert (det == det[0]).all()  # custom entropy: direct path, no seed mix


def test_modmatmul_np_int64_min_entries_exact():
    """np.abs(INT64_MIN) wraps back to INT64_MIN, so an operand holding it
    used to poison the fast-path magnitude bound into blessing a matmul
    whose raw products overflow. Such entries must take the pre-reduced
    (robust) path and still produce exact residues."""
    m = (1 << 31) - 1  # below MAX_SAFE_MODULUS: the int64 ladder runs
    lo = np.iinfo(np.int64).min
    A = np.array([[lo, 3], [2, lo]], dtype=np.int64)
    B = np.array([[5, lo], [lo, 7]], dtype=np.int64)
    got = modmatmul_np(A, B, m)
    exact = A.astype(object) @ B.astype(object)
    want = np.vectorize(lambda v: rust_rem_int(int(v), m), otypes=[np.int64])(exact)
    np.testing.assert_array_equal(rust_rem_np(got, m) % m, want % m)
    assert (np.abs(got) < m).all()  # representatives stay in (-m, m)
