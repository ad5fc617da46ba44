"""Faults the tests hand the harness through a traffic file's dotted names:
the benchmark must call a run with any of them incorrect."""

from __future__ import annotations


def flipped_reconstruct(clerk_sums, indices, scheme, dim):
    """The program's reconstruct with one element of the aggregate off by one."""
    from sda_tpu.ops.shamir import reconstruct_clerk_sums_host

    out = reconstruct_clerk_sums_host(clerk_sums, indices, scheme, dim).copy()
    out[dim // 2] += 1
    return out


def keyless_chunk_engine(entry, plan, mesh):
    """A chunk engine that ignores the round's key: every round draws the same
    share randomness, so the aggregate stays right and the clerk sums repeat."""
    import jax

    return lambda secrets, key: entry(secrets, jax.random.key(7), plan)


def late_raising_epilogue(entry, plan):
    """The sum-first epilogue, which raises from its third call on (warm-up
    and the window's first round pass)."""
    calls = {"n": 0}

    def epilogue(acc):
        calls["n"] += 1
        if calls["n"] > 2:
            raise RuntimeError("the epilogue fell over")
        return entry(acc, plan)[0]

    return epilogue
