"""The masked fabric entry (``parallel/masked.py``) and the recipient's side
of it, against the benchmark's plain references
(``benchmark/reference_chacha.py``, ``benchmark/reference.py``, which import
nothing of the program): masks, masked values, seeds and counts handed on,
the unmasked aggregate; the reference itself against the host expansion and a
literal vector; the slack check; the compaction that took the scatter's
place."""

import pathlib
import sys

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import reference, reference_chacha  # noqa: E402
from sda_tpu import telemetry  # noqa: E402
from sda_tpu.ops import chacha, chacha_pallas, find_packed_parameters  # noqa: E402
from sda_tpu.protocol import ChaChaMasking, FullMasking, PackedShamirSharing  # noqa: E402

DIM, ROWS, CHUNK = 61, 24, 6
#: djb's ChaCha20, zero key, zero nonce, block 0 (draft-strombergson-chacha-test-vectors, TC1)
ZERO_KEY_BLOCK = [
    0xADE0B876, 0x903DF1A0, 0xE56A5D40, 0x28BD8653, 0xB819D2BD, 0x1AED8DA0, 0xCCEF36A8,
    0xC70D778B, 0x7C5941DA, 0x8D485751, 0x3FE02477, 0x374AD8B8, 0xF4B8436A, 0x1CA11815,
    0x69B687C3, 0x8665EEB2,
]


@pytest.fixture(scope="module")
def field():
    """The benchmark's 61-bit scheme at a tiny dim: ``(scheme, plan, masking)``."""
    from sda_tpu.ops.jaxcfg import ensure_x64
    from sda_tpu.parallel.engine import make_plan

    ensure_x64()  # before the first array is placed: 61-bit values
    p, w2, w3 = find_packed_parameters(5, 2, 8, min_modulus_bits=60, seed=0)
    scheme = PackedShamirSharing(5, 8, 2, p, w2, w3)
    return scheme, make_plan(scheme, DIM), ChaChaMasking(int(p), DIM, 128)


def ticks(name, **labels):
    """What the counter ``name`` reads under these labels."""
    return sum(
        c["value"] for c in telemetry.snapshot()["counters"]
        if c["name"] == name and all(c["labels"].get(k) == v for k, v in labels.items())
    )


def secrets_of(seed, p, rows=ROWS):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 1 << 60, size=(rows, DIM), dtype=np.int64) % p


@pytest.mark.parametrize("backend", ["jnp", "interpret", "auto"])
def test_masked_entry_agrees_with_the_references_row_by_row(field, backend):
    """Chunk by chunk: the seeds are the step's own stream, the counts are the
    window's, the accumulator is the entry's over ``(x + mask) mod p`` with
    the reference's masks; over all chunks the recipient's unmask gives the
    plain column sums."""
    import jax
    import jax.numpy as jnp

    from sda_tpu.crypto.masking import ChaChaMasker
    from sda_tpu.ops.shamir import reconstruct_clerk_sums_host
    from sda_tpu.parallel import masked
    from sda_tpu.parallel.sumfirst import clerk_sums_from_limb_acc, value_limb_sums_chunk

    scheme, plan, masking = field
    p = plan.modulus
    secrets = secrets_of(3, p)
    fn = jax.jit(masked.masked_chunk(value_limb_sums_chunk, plan, masking, backend=backend))
    pairs = chacha_pallas._window_pairs(DIM, p)
    acc, uploads = 0, []
    for i in range(ROWS // CHUNK):
        chunk = secrets[i * CHUNK : (i + 1) * CHUNK]
        key = jax.random.fold_in(jax.random.key(11), i)
        out, seeds, counts = fn(jnp.asarray(chunk), key)
        share_key, mask_key = jax.random.split(key)
        assert np.array_equal(seeds, jax.random.bits(mask_key, (CHUNK, 4), dtype=jnp.uint32))
        want_masks = reference_chacha.masks(np.asarray(seeds), DIM, p)
        _masks, want_counts = reference_chacha.expand(np.asarray(seeds), DIM, p, pairs)
        assert np.array_equal(counts, want_counts) and counts.dtype == jnp.int32
        masked_rows = ((chunk.astype(object) + want_masks.astype(object)) % p).astype(np.int64)
        assert not np.array_equal(masked_rows, chunk)
        want_out = value_limb_sums_chunk(jnp.asarray(masked_rows), share_key, plan)
        assert np.array_equal(out, want_out)
        acc = acc + np.asarray(out)
        uploads.extend(np.asarray(seeds).astype(np.int64))
        assert masked.count_short_windows(counts, DIM) == 0
    clerk_sums, _ = clerk_sums_from_limb_acc(acc, plan)
    revealed = np.mod(
        np.asarray(reconstruct_clerk_sums_host(clerk_sums, list(range(7)), scheme, DIM)), p
    )
    halves = np.asarray(reference.half_sums(jnp.asarray(secrets)))
    plain = reference.aggregate(halves, reference.strided_columns(secrets), p, 1, ROWS)
    assert not np.array_equal(revealed, plain), "the clerks' sums carry no mask"
    masker = ChaChaMasker(p, DIM, 128)
    got = np.mod(masker.unmask(masker.combine(uploads), revealed), p)
    assert np.array_equal(got, plain)
    want_mask = reference_chacha.mask_sum(np.stack(uploads), DIM, p)
    assert np.array_equal(masker.combine(uploads), want_mask)


def test_masked_step_on_the_compaction_kernel_cancels_against_the_recipients_fold(field):
    """At a dim whose window fills a lane tile both sides compact in the
    kernel ``chacha_compact`` (its source, on the interpreter): the step's
    masks cancel against the recipient's fold, and the counter reads the rows
    each side compacted under the path taken."""
    import jax
    import jax.numpy as jnp

    from sda_tpu.ops.shamir import reconstruct_clerk_sums_host
    from sda_tpu.parallel import masked
    from sda_tpu.parallel.engine import make_plan
    from sda_tpu.parallel.sumfirst import clerk_sums_from_limb_acc, value_limb_sums_chunk

    scheme, small_plan, _masking = field
    p, dim = small_plan.modulus, 150
    plan, masking = make_plan(scheme, dim), ChaChaMasking(int(p), dim, 128)
    window = (chacha_pallas._window_pairs(dim, p) * 2 + 15) // 16 * 8
    assert chacha_pallas._compact_fits(window, dim) and not chacha_pallas._compact_fits(88, DIM)

    def compacted(path):
        return ticks("sda_crypto_chacha_compactions_total", path=path)

    rng = np.random.default_rng(5)
    secrets = rng.integers(0, 1 << 60, size=(CHUNK, dim), dtype=np.int64) % p
    before, twin_before = compacted("interpret"), compacted("jnp")
    fn = jax.jit(masked.masked_chunk(value_limb_sums_chunk, plan, masking, backend="interpret"))
    acc, seeds, counts = fn(jnp.asarray(secrets), jax.random.key(7))
    assert compacted("interpret") == before + CHUNK
    assert masked.count_short_windows(counts, dim) == 0
    clerk_sums, _ = clerk_sums_from_limb_acc(np.asarray(acc), plan)
    revealed = np.mod(
        np.asarray(reconstruct_clerk_sums_host(clerk_sums, list(range(7)), scheme, dim)), p
    )
    plain = np.array([sum(int(v) for v in column) % p for column in secrets.T])
    assert not np.array_equal(revealed, plain), "the clerks' sums carry no mask"
    mask_sum = chacha_pallas.combine_masks_device(
        np.asarray(seeds), dim, p, chunk=CHUNK // 2, backend="interpret"
    )
    assert compacted("interpret") == before + CHUNK + CHUNK // 2  # the fold is traced once
    assert compacted("jnp") == twin_before
    assert np.array_equal(mask_sum, reference_chacha.mask_sum(np.asarray(seeds), dim, p))
    assert np.array_equal((revealed - np.asarray(mask_sum)) % p, plain)


def test_masked_entry_refuses_what_it_cannot_mask(field):
    from sda_tpu.parallel import masked
    from sda_tpu.parallel.sumfirst import value_limb_sums_chunk

    _scheme, plan, masking = field
    with pytest.raises(TypeError, match="ChaCha"):
        masked.masked_chunk(value_limb_sums_chunk, plan, FullMasking(plan.modulus))
    with pytest.raises(ValueError, match="plan's"):
        masked.masked_chunk(value_limb_sums_chunk, plan, ChaChaMasking(plan.modulus, DIM + 1, 128))
    with pytest.raises(ValueError, match="seed_bitsize"):
        masked.masked_chunk(value_limb_sums_chunk, plan, ChaChaMasking(plan.modulus, DIM, 512))


def test_auto_backend_is_decided_where_the_program_is_lowered():
    """For the CPU the jnp twin, and no kernel in the lowered text; the
    compile rehearsal (``tests/benchmark``) lowers the same program for a
    described v5e and finds the kernel there."""
    import jax
    import jax.numpy as jnp

    seeds = jnp.arange(8, dtype=jnp.uint32).reshape(2, 4)
    expand = jax.jit(chacha_pallas.expand_seeds_counts, static_argnums=(1, 2, 3))
    assert "tpu_custom_call" not in expand.lower(seeds, 19, 433, "auto").as_text()
    got, _counts = expand(seeds, 19, 433, "auto")
    want = np.stack([chacha.expand_seed(np.asarray(s), 19, 433) for s in seeds])
    assert np.array_equal(got, want)


def test_reference_chacha_known_answer_and_host_expansion():
    import jax

    jax.config.update("jax_enable_x64", True)
    stream = np.asarray(reference_chacha.keystream(np.zeros((1, 4), np.uint32), 2))[0]
    assert list(stream[:16]) == ZERO_KEY_BLOCK
    assert np.array_equal(stream.reshape(2, 16), chacha.chacha_blocks(np.zeros(4, np.uint32), 0, 2))
    rng = np.random.default_rng(0)
    seeds = rng.integers(0, 1 << 32, size=(5, 4), dtype=np.uint64).astype(np.uint32)
    p61 = int(find_packed_parameters(5, 2, 8, min_modulus_bits=60, seed=0)[0])
    for modulus, dim in ((p61, 61), (1 << 62, 40), (1 << 20, 33), (433, 19), (1 << 63, 25)):
        want = np.stack([chacha.expand_seed(s, dim, modulus) for s in seeds])
        assert np.array_equal(reference_chacha.masks(seeds, dim, modulus), want), modulus
        exact = np.array([sum(int(v) for v in column) % modulus for column in want.T])
        assert np.array_equal(reference_chacha.mask_sum(seeds, dim, modulus, block=2), exact)


@pytest.mark.parametrize("modulus", [1 << 62, 1 << 20, 433, (1 << 61) - 1])
def test_reference_zone_is_rand03s_not_the_textbooks(modulus):
    """A power of two divides 2^64, and rand 0.3 still rejects the top
    ``modulus`` values; the program's one definition agrees."""
    zone = reference_chacha.zone(modulus)
    assert zone == chacha.rand03_zone(modulus)
    assert zone % modulus == 0 and (1 << 64) - 2 * modulus < zone < 1 << 64
    if modulus & (modulus - 1) == 0:
        assert zone == (1 << 64) - modulus  # the textbook's would be 2^64


def test_reference_widens_its_window_where_a_row_comes_short():
    seeds = np.arange(12, dtype=np.uint32).reshape(3, 4)
    want = np.stack([chacha.expand_seed(s, 40, 1 << 63) for s in seeds])
    short, counts = reference_chacha.expand(seeds, 40, 1 << 63, pairs=48)
    assert int(np.min(counts)) < 40  # half the draws are rejected at 2^63
    assert np.array_equal(reference_chacha.masks(seeds, 40, 1 << 63), want)


def test_slack_check_fires_on_a_short_window(field, monkeypatch):
    """A window forced too short: the step's counts say so, the check counts
    the rows, and the recipient's fold takes the host's expansion."""
    import jax
    import jax.numpy as jnp

    from sda_tpu.parallel import masked
    from sda_tpu.parallel.sumfirst import value_limb_sums_chunk

    _scheme, plan, masking = field
    p = plan.modulus
    monkeypatch.setattr(chacha_pallas, "_window_pairs", lambda dim, modulus: dim + 1)
    fn = masked.masked_chunk(value_limb_sums_chunk, plan, masking, backend="jnp")
    _acc, seeds, counts = fn(jnp.asarray(secrets_of(1, p, CHUNK)), jax.random.key(2))
    assert int(jnp.min(counts)) < DIM

    before = ticks("sda_mask_slack_exhausted_total", side="participant")
    rows_before = ticks("sda_fabric_masked_rows_total")
    short = masked.count_short_windows(counts, DIM)
    assert short == int(np.count_nonzero(np.asarray(counts) < DIM)) > 0
    assert ticks("sda_mask_slack_exhausted_total", side="participant") == before + short
    assert ticks("sda_fabric_masked_rows_total") == rows_before + CHUNK
    recipient_before = ticks("sda_mask_slack_exhausted_total", side="recipient")
    total = chacha_pallas.combine_masks_device(
        np.asarray(seeds), DIM, p, chunk=CHUNK, backend="jnp"
    )
    assert ticks("sda_mask_slack_exhausted_total", side="recipient") == recipient_before + short
    assert np.array_equal(total, reference_chacha.mask_sum(np.asarray(seeds), DIM, p))


@pytest.mark.parametrize("dim,window", [(5, 9), (40, 64), (100, 107), (64, 200)])
def test_first_accepted_is_the_stable_compaction(dim, window):
    """Against the plain definition, on random acceptance patterns, rows that
    come short among them: the first ``dim`` accepted pairs in order, zeros
    past a row's last accepted draw."""
    import jax.numpy as jnp

    rng = np.random.default_rng(dim)
    rows = 12
    hi = rng.integers(1, 1 << 32, size=(rows, window), dtype=np.uint64).astype(np.uint32)
    lo = rng.integers(1, 1 << 32, size=(rows, window), dtype=np.uint64).astype(np.uint32)
    ok = rng.random((rows, window)) < rng.uniform(0.5, 1.0, size=(rows, 1))
    ok[0], ok[1] = True, False  # nothing rejected; nothing accepted
    got_hi, got_lo = chacha_pallas._first_accepted(
        jnp.asarray(hi), jnp.asarray(lo), jnp.asarray(ok), dim
    )
    checked = 0
    for r in range(rows):
        kept = np.flatnonzero(ok[r])
        if len(kept) < dim:
            continue  # a short row's mask is undefined; its count says so
        checked += 1
        assert np.array_equal(got_hi[r], hi[r, kept[:dim]]), r
        assert np.array_equal(got_lo[r], lo[r, kept[:dim]]), r
    assert checked >= 2 and not np.any(np.asarray(got_hi[1]))


def test_the_fold_handle_is_the_program_the_reveal_runs(monkeypatch):
    """``fold_chunk_jit`` hands out the one jitted fold that
    ``combine_masks_device`` runs, under the recipient's scopes; the masker's
    ``chunk`` reaches it, and its spans say which road the combine took."""
    import jax.numpy as jnp

    from sda_tpu.crypto.masking import ChaChaMasker

    fold = chacha_pallas.fold_chunk_jit()
    assert fold is chacha_pallas.fold_chunk_jit()
    seeds = np.arange(24, dtype=np.uint32).reshape(6, 4)
    text = fold.lower(jnp.asarray(seeds[:2]), 19, 433, "jnp").compile().as_text()
    assert "fabric.unmask/expand" in text and "fabric.unmask/sum" in text
    shapes = []

    def spy(batch, dim, modulus, backend):
        shapes.append(batch.shape)
        return fold(batch, dim, modulus, backend)

    monkeypatch.setattr(chacha_pallas, "fold_chunk_jit", lambda: spy)
    monkeypatch.setattr(ChaChaMasker, "DEVICE_COMBINE_THRESHOLD", 1)
    masker = ChaChaMasker(433, 19, 128)
    uploads = list(seeds.astype(np.int64))
    combined = masker.combine(uploads, chunk=2)
    assert shapes == [(2, 4)] * 3
    want = np.stack([chacha.expand_seed(s, 19, 433) for s in seeds]).sum(axis=0) % 433
    assert np.array_equal(combined, want)
    assert np.array_equal(np.mod(masker.unmask(combined, (want + 5) % 433), 433), np.full(19, 5))
    names = {s["name"]: s["attrs"] for s in telemetry.spans(name="fabric.unmask")}
    assert names["fabric.unmask.combine"]["path"] == "device"
    assert "fabric.unmask.subtract" in names
