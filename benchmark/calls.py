"""How the harness calls into the program: one small adapter per calling
convention, named by dotted path in a traffic file (``engine_call``,
``epilogue_call``). A program entry with a new convention needs a new
adapter in a new file, and no edit here.

No adapter passes a ``draw``: the share randomness is whatever the program
draws by default.
"""

from __future__ import annotations


def chunk_engine(entry, plan, mesh):
    """``entry(secrets, key, plan) -> accumulator`` over one chunk of rows."""
    if mesh is not None:
        raise ValueError("a chunk engine takes no mesh; use mesh_engine")
    return lambda secrets, key: entry(secrets, key, plan)


def mesh_engine(entry, plan, mesh):
    """``entry(plan, mesh) -> fn(secrets_sharded, key) -> accumulator``."""
    if mesh is None:
        raise ValueError("a mesh engine needs the traffic file's mesh")
    return entry(plan, mesh)


def limb_acc_epilogue(entry, plan):
    """``entry(acc, plan) -> (clerk_sums (n, B), value_sums)``."""
    return lambda acc: entry(acc, plan)[0]


def limb_recombine_epilogue(entry, plan):
    """``entry(acc (W, B, n), p) -> (B, n)`` canonical; clerk-major here."""
    return lambda acc: entry(acc, plan.modulus).T
