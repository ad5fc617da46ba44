"""Faults of the host-fed round, handed to the harness through a traffic
file's dotted names (as ``faulty.py``'s are): drivers whose feed breaks one of
the configuration's guarantees. The benchmark must call a run with any of
them incorrect."""

from __future__ import annotations

import dataclasses


def _driver_with(feed, scheme, dim, entry, chunk):
    """The program's driver for these, but for its feed."""
    from sda_tpu.parallel import FoldRound, fold_round

    real = fold_round(scheme, dim, entry, chunk)
    faulty = type("FaultyRound", (FoldRound,), {"fold_host_rows": feed})
    return faulty(**{f.name: getattr(real, f.name) for f in dataclasses.fields(real)})


#: device chunks kept from the first round, by the driver that kept them
_KEPT = {}


def _keeping(self, blocks, key, *, in_flight):
    """Rows cross once: the first round's chunks stay on the device and every
    later round folds them again, whatever the host cohort holds by then. It
    counts the bytes it should have fed, so that only the aggregate tells."""
    import jax

    from sda_tpu import telemetry

    if id(self) not in _KEPT:
        _KEPT[id(self)] = [
            jax.device_put(block[start : start + self.chunk])
            for block in blocks for start in range(0, block.shape[0], self.chunk)
        ]
    telemetry.counter("sda_fabric_fed_bytes_total").inc(sum(b.nbytes for b in blocks))
    return self.fold_chunks(_KEPT[id(self)], key)


def _dropping(self, blocks, key, *, in_flight):
    """Not every row: the round's last block never crosses."""
    from sda_tpu.parallel import FoldRound

    return FoldRound.fold_host_rows(self, list(blocks)[:-1], key, in_flight=in_flight)


def _greedy(self, blocks, key, *, in_flight):
    """One block more alive than the caller allowed."""
    from sda_tpu.parallel import FoldRound

    return FoldRound.fold_host_rows(self, blocks, key, in_flight=in_flight + 1)


def keeping_driver(scheme, dim, entry, chunk):
    return _driver_with(_keeping, scheme, dim, entry, chunk)


def dropping_driver(scheme, dim, entry, chunk):
    return _driver_with(_dropping, scheme, dim, entry, chunk)


def greedy_driver(scheme, dim, entry, chunk):
    return _driver_with(_greedy, scheme, dim, entry, chunk)
