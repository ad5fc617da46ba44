"""Faults of the masked host-fed round, handed to the harness through a
traffic file's dotted names (as ``faulty_hostfed.py``'s are): drivers whose
feed or whose step breaks one of the configuration's guarantees. The
benchmark must call a run with any of them incorrect, each by the comparison
that is there for it."""

from __future__ import annotations

import dataclasses


def _real(scheme, dim, entry, chunk, masking):
    from sda_tpu.parallel import fold_round

    return fold_round(scheme, dim, entry, chunk, masking=masking)


def _with_feed(feed, real):
    """The program's driver, but for its feed."""
    from sda_tpu.parallel import FoldRound

    faulty = type("FaultyRound", (FoldRound,), {"fold_host_rows": feed})
    return faulty(**{f.name: getattr(real, f.name) for f in dataclasses.fields(real)})


def _with_step(wrap, real):
    """The program's driver, but for its jitted step: ``wrap(real)`` gives
    ``masked_step(acc, chunk, key, i) -> (acc, seeds, counts)``."""
    import jax

    return dataclasses.replace(real, step=jax.jit(wrap(real)))


#: device chunks kept from the first round, by the driver that kept them
_KEPT = {}


def _keeping(self, blocks, key, *, in_flight):
    """Rows cross once: the first round's chunks stay on the device and every
    later round masks and folds them again, whatever the host cohort holds by
    then. It counts the bytes and the seeds it should have fed, so that only
    the aggregate tells."""
    import jax

    from sda_tpu import telemetry

    if id(self) not in _KEPT:
        _KEPT[id(self)] = [
            jax.device_put(block[start : start + self.chunk])
            for block in blocks for start in range(0, block.shape[0], self.chunk)
        ]
    telemetry.counter("sda_fabric_fed_bytes_total").inc(sum(b.nbytes for b in blocks))
    telemetry.counter("sda_fabric_fed_seeds_total").inc(sum(b.shape[0] for b in blocks))
    return self.fold_chunks(_KEPT[id(self)], key)


def _dropping(self, blocks, key, *, in_flight):
    """Not every row: the round's last block never crosses."""
    from sda_tpu.parallel import FoldRound

    return FoldRound.fold_host_rows(self, list(blocks)[:-1], key, in_flight=in_flight)


def _losing_seeds(self, blocks, key, *, in_flight):
    """Every row crosses and is masked, but the last step's seeds and counts
    never reach the recipient: its masks stay in the aggregate."""
    from sda_tpu.parallel import FoldRound

    acc, seeds, counts = FoldRound.fold_host_rows(self, blocks, key, in_flight=in_flight)
    return acc, seeds[:-1], counts[:-1]


def _adding_nothing(real):
    """A mask stage that adds nothing: the seeds and counts are the real
    step's, and the rows go to the entry as they are, on the key the real
    step's entry gets, so the clerks' sums carry no mask."""
    import jax

    def masked_step(acc, chunk, key, i):
        _masked, seeds, counts = real.step(acc, chunk, key, i)
        share_key = jax.random.split(jax.random.fold_in(key, i))[0]
        return acc + real.entry(chunk, share_key, real.plan), seeds, counts

    return masked_step


def _short_windows(real):
    """Every row's rejection window ran out: the counts say no draw was
    accepted."""

    def masked_step(acc, chunk, key, i):
        acc, seeds, counts = real.step(acc, chunk, key, i)
        return acc, seeds, counts * 0

    return masked_step


def keeping_driver(scheme, dim, entry, chunk, masking=None):
    return _with_feed(_keeping, _real(scheme, dim, entry, chunk, masking))


def dropping_driver(scheme, dim, entry, chunk, masking=None):
    return _with_feed(_dropping, _real(scheme, dim, entry, chunk, masking))


def seed_losing_driver(scheme, dim, entry, chunk, masking=None):
    return _with_feed(_losing_seeds, _real(scheme, dim, entry, chunk, masking))


def unmasking_driver(scheme, dim, entry, chunk, masking=None):
    real = _real(scheme, dim, entry, chunk, masking)
    return real if masking is None else _with_step(_adding_nothing, real)


def short_window_driver(scheme, dim, entry, chunk, masking=None):
    real = _real(scheme, dim, entry, chunk, masking)
    return real if masking is None else _with_step(_short_windows, real)
