"""Masking schemes: None / Full / ChaCha.

Semantics mirror /root/reference/client/src/crypto/masking/: the participant
produces ``(recipient_mask, masked_secrets)``; the recipient later combines
all participants' masks and subtracts. Vectors are numpy int64 throughout
(the reference loops element-wise; here each op is one vectorized kernel).
"""

from __future__ import annotations

import numpy as np

from .. import telemetry
from ..native import chacha_combine, chacha_expand as expand_seed
from ..ops.modular import mod_sum_wide_np, rust_rem_np
from ..ops.rng import uniform_mod_host
from ..protocol import ChaChaMasking, FullMasking, NoMasking


class SecretMasker:
    def mask(self, secrets: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """secrets -> (mask-for-recipient, masked-secrets-for-committee)."""
        raise NotImplementedError


class MaskCombiner:
    def combine(self, masks: list) -> np.ndarray:
        """Combine all participants' uploaded masks into one."""
        raise NotImplementedError

    def accumulator(self) -> "MaskAccumulator":
        """Streaming equivalent of ``combine``: fold the cohort's masks
        chunk by chunk, holding one chunk plus one combined partial at a
        time — the ``sumfirst`` discipline (parallel/sumfirst.py) applied
        to the reveal plane, so recipient memory stays flat in cohort
        size. ``finish()`` is byte-identical to the monolithic
        ``combine`` over the concatenated chunks (see MaskAccumulator)."""
        return MaskAccumulator(self)


class MaskAccumulator:
    """Chunk-by-chunk mask folding with an exactness contract: every
    per-chunk partial (``combine``) and every pairwise fold below is a
    CANONICAL residue in ``[0, m)``, and modular addition of canonical
    representatives is associative — so the folded result is
    byte-identical to the monolithic combine REGARDLESS of chunk
    boundaries (asserted across the full matrix in
    tests/test_reveal_chunks.py). The pairwise fold adds in uint64 (two
    canonical values each < m sum below 2**64 for any m <= 2**63 —
    the same width discipline as ``chacha_combine``'s host path)."""

    def __init__(self, combiner: MaskCombiner):
        self._combiner = combiner
        self._acc: np.ndarray | None = None

    def fold(self, masks: list) -> None:
        if not masks:
            return
        partial = self._combiner.combine(masks)
        if self._acc is None or self._acc.size == 0:
            self._acc = partial
        elif partial.size:
            total = self._acc.astype(np.uint64) + partial.astype(np.uint64)
            self._acc = (total % np.uint64(self._combiner.modulus)).astype(np.int64)

    def finish(self) -> np.ndarray:
        if self._acc is None:
            # no chunks at all: each scheme's own empty-cohort shape
            # (NoMasking/Full: empty vector; ChaCha: zeros(dimension))
            return self._combiner.combine([])
        return self._acc


class SecretUnmasker:
    def unmask(self, mask: np.ndarray, masked: np.ndarray) -> np.ndarray:
        raise NotImplementedError


class NoMasker(SecretMasker, MaskCombiner, SecretUnmasker):
    """Zero masking: empty mask, secrets pass through (masking/none.rs)."""

    def mask(self, secrets):
        return np.empty(0, dtype=np.int64), np.asarray(secrets, dtype=np.int64).copy()

    def combine(self, masks):
        assert all(len(m) == 0 for m in masks)
        return np.empty(0, dtype=np.int64)

    def unmask(self, mask, masked):
        assert len(mask) == 0
        return np.asarray(masked, dtype=np.int64).copy()


class FullMasker(SecretMasker, MaskCombiner, SecretUnmasker):
    """Per-element uniform masks from OS entropy (masking/full.rs)."""

    def __init__(self, modulus: int):
        self.modulus = modulus

    def mask(self, secrets):
        secrets = np.asarray(secrets, dtype=np.int64)
        masks = uniform_mod_host(secrets.shape, self.modulus)
        masked = rust_rem_np(secrets + masks, self.modulus)
        return masks, masked

    def combine(self, masks):
        if not masks:
            return np.empty(0, dtype=np.int64)
        stack = np.stack([np.asarray(m, dtype=np.int64) for m in masks])
        return mod_sum_wide_np(stack, self.modulus, axis=0)

    def unmask(self, mask, masked):
        return rust_rem_np(np.asarray(masked, np.int64) - np.asarray(mask, np.int64), self.modulus)


class ChaChaMasker(SecretMasker, MaskCombiner, SecretUnmasker):
    """Seed-compressed masks (masking/chacha.rs): upload only the seed.

    The uploaded "mask" is the seed's u32 words as i64s (matching the
    reference's wire shape, chacha.rs:48-52), and the expansion is
    BIT-EXACT to the reference's rand-0.3 ``ChaChaRng::from_seed`` +
    ``gen_range(0, m)`` (see ``sda_tpu.ops.chacha`` module doc; oracle
    test in tests/test_ops_field.py) — a mixed deployment (reference
    participant with this recipient, or vice versa) unmasks correctly.
    """

    def __init__(self, modulus: int, dimension: int, seed_bitsize: int):
        self.modulus = modulus
        self.dimension = dimension
        self.seed_words = (seed_bitsize + 31) // 32

    def mask(self, secrets):
        secrets = np.asarray(secrets, dtype=np.int64)
        if len(secrets) != self.dimension:
            raise ValueError("dimension mismatch")
        seed = uniform_mod_host((self.seed_words,), 1 << 32).astype(np.uint32)
        mask = expand_seed(seed, self.dimension, self.modulus)
        masked = rust_rem_np(secrets + mask, self.modulus)
        return seed.astype(np.int64), masked

    #: below this many expanded elements the host loop beats device dispatch
    DEVICE_COMBINE_THRESHOLD = 1 << 22

    def combine(self, seeds, *, chunk: int | None = None, mesh=None):
        """Sum of the seeds' masks mod m. ``chunk`` is how many seeds one
        device fold expands (``combine_masks_device``'s default fits its own
        memory budget; a recipient that shares the chip says less). Given a
        ``mesh`` (a device mesh: the masked round's, ``FoldRound.unmask``) the
        device fold runs on every chip of it, ``chunk`` seeds a chip a fold;
        ``seeds`` are then one vector of words a participant, as ever, or
        ``(rows, words)`` uint32 device arrays sharded over that mesh, which
        are folded where they lie."""
        placed = mesh is not None and len(seeds) > 0 and all(np.ndim(s) == 2 for s in seeds)
        if placed:
            seed_rows, count = list(seeds), sum(int(s.shape[0]) for s in seeds)
        else:
            # every upload's int64 words to uint32 in one call (a negative
            # word wraps as each row's own cast would): (rows, words)
            seed_rows = np.asarray(seeds, dtype=np.int64).astype(np.uint32)
            count = len(seed_rows)
        on_device = count * self.dimension >= self.DEVICE_COMBINE_THRESHOLD
        if placed and not on_device:  # too few to be worth the chips: as uploaded
            seed_rows = np.concatenate([np.asarray(s) for s in seed_rows])
        with telemetry.span(
            "fabric.unmask.combine", seeds=count,
            path="device" if on_device else "host",
            chips=mesh.size if on_device and mesh is not None else 1,
        ):
            if on_device:
                # reveal hot loop (receive.rs:102-118): expand + sum on device
                # (ops/chacha_pallas.py). Above the threshold this path runs or
                # raises — a device failure must not hide behind the host loop.
                from ..ops.chacha_pallas import combine_masks_device

                return np.asarray(
                    combine_masks_device(
                        seed_rows, self.dimension, self.modulus, chunk=chunk, mesh=mesh
                    )
                )
            if not count:
                return np.zeros(self.dimension, dtype=np.int64)
            # one C call expands + folds the whole cohort (19x the numpy loop;
            # falls back to it when the extension isn't built)
            return chacha_combine(seed_rows, self.dimension, self.modulus)

    def unmask(self, mask, masked):
        with telemetry.span("fabric.unmask.subtract", shape=np.shape(masked)):
            return rust_rem_np(
                np.asarray(masked, np.int64) - np.asarray(mask, np.int64), self.modulus
            )


def new_secret_masker(scheme) -> SecretMasker:
    return _dispatch(scheme)


def new_mask_combiner(scheme) -> MaskCombiner:
    return _dispatch(scheme)


def new_secret_unmasker(scheme) -> SecretUnmasker:
    return _dispatch(scheme)


def _dispatch(scheme):
    if isinstance(scheme, NoMasking):
        return NoMasker()
    if isinstance(scheme, FullMasking):
        return FullMasker(scheme.modulus)
    if isinstance(scheme, ChaChaMasking):
        return ChaChaMasker(scheme.modulus, scheme.dimension, scheme.seed_bitsize)
    raise TypeError(f"unknown masking scheme {scheme!r}")
