"""The one general traffic generator: reads a traffic file's parameters and
makes the resident input on the device, from the seed.

A traffic mix is data (``benchmark/traffic/<name>.json``): rows, passes,
chunk, mesh, whether the step makes the share matmul, and by dotted name the
round that drives it (``round``, a module of the interface that
:mod:`benchmark.rounds` describes; absent means
``benchmark.rounds.packed_fold``). Whatever else the file holds (a round's
engine entries, say) is kept as ``params`` for that round alone to read and
to check. The resident input is one ``(chunk, dim)`` array per chunk step;
over a mesh a chunk's rows are sharded over ``p`` (and the dim over ``d``),
so every chip makes and keeps its own rows and a step moves none of them.

The plain reference's sums are taken in the same jitted call
(:mod:`benchmark.reference`), so the input is read once in set-up, and chunk
by chunk: on a TPU a program that hands back an int64 array holds it twice
(as 32-bit halves inside, whole at its boundary), so one program making the
whole 8 GB block would not fit the chip.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import pathlib

from benchmark import reference


#: the round of a traffic file that names none
DEFAULT_ROUND = "benchmark.rounds.packed_fold"


@dataclasses.dataclass(frozen=True)
class Traffic:
    name: str
    share_matmul_in_step: bool  # the chunk step shares every participant (int8 dots)
    rows: int
    passes: int
    chunk: int
    mesh: dict | None
    round: str = DEFAULT_ROUND  # dotted module path of the round this mix drives
    params: dict = dataclasses.field(default_factory=dict)  # the whole file, for its round

    @property
    def steps_per_pass(self) -> int:
        return self.rows // self.chunk

    @property
    def mesh_shape(self) -> tuple | None:
        """``(p, d)``, or ``None`` for a one-chip mix."""
        if not self.mesh:
            return None
        return int(self.mesh["p"]), int(self.mesh.get("d", 1))

    @property
    def chips(self) -> int:
        return 1 if not self.mesh else self.mesh_shape[0] * self.mesh_shape[1]


def load(path: pathlib.Path) -> Traffic:
    raw = json.loads(pathlib.Path(path).read_text())
    fields = {f.name for f in dataclasses.fields(Traffic)} - {"params"}
    require(raw, fields - {"round"}, path)
    t = Traffic(**{k: raw[k] for k in fields & raw.keys()}, params=raw)
    if t.rows <= 0 or t.chunk <= 0 or t.passes <= 0 or t.rows % t.chunk:
        raise ValueError(f"{path}: rows must be a positive multiple of chunk")
    if t.mesh and t.chunk % t.mesh_shape[0]:
        raise ValueError(f"{path}: chunk must divide over mesh p")
    return t


def require(params: dict, keys, where) -> None:
    """Raise where a traffic file lacks one of the keys its reader needs."""
    missing = set(keys) - params.keys()
    if missing:
        raise ValueError(f"{where}: traffic file lacks {sorted(missing)}")


def resolve(dotted: str):
    """``package.module.attr`` -> the object."""
    module, _, attr = dotted.rpartition(".")
    return getattr(importlib.import_module(module), attr)


def make_mesh(traffic: Traffic, devices):
    """The traffic file's mesh over the first devices handed in, axes
    ``('p', 'd')``; ``None`` for a one-chip mix."""
    if not traffic.mesh:
        return None
    import numpy as np
    from jax.sharding import Mesh

    p, d = traffic.mesh_shape
    if len(devices) < p * d:
        raise ValueError(f"mesh p={p} x d={d} needs {p * d} devices")
    return Mesh(np.array(devices[: p * d]).reshape(p, d), ("p", "d"))


def _sharding(devices, mesh, *axes):
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

    if mesh is None:
        return SingleDeviceSharding(devices[0])
    return NamedSharding(mesh, PartitionSpec(*axes))


def chunk_sharding(devices, mesh):
    """A chunk's rows over ``p``, its dim over ``d``."""
    return _sharding(devices, mesh, "p", "d")


def replicated(devices, mesh):
    return _sharding(devices, mesh)


def input_dtype(modulus: int):
    """int32 where every canonical value fits it, else int64: the type the
    program's engines keep the big tensor in."""
    import jax.numpy as jnp

    return jnp.int32 if modulus <= (1 << 31) else jnp.int64


def chunk_maker(traffic: Traffic, dim: int, modulus: int, devices, mesh):
    """The jitted ``make(key, i, half_sums) -> (chunk, half_sums, columns)``:
    chunk ``i`` of the resident input, ``(chunk, dim)`` seeded uniform values
    over ``[0, 2^(bits(p)-1))`` (canonical, zero bias), each shard made on its
    own chip; the reference's running half sums with this chunk added; and
    the chunk's strided columns for the host's own sums. One compile."""
    import jax
    import jax.numpy as jnp

    nbits = int(modulus).bit_length() - 1
    dtype = input_dtype(modulus)
    bits_dtype = jnp.uint32 if nbits <= 32 else jnp.uint64
    mask = bits_dtype((1 << nbits) - 1)

    def make(key, i, halves):
        bits = jax.random.bits(
            jax.random.fold_in(key, i), (traffic.chunk, dim), dtype=bits_dtype
        )
        chunk = (bits & mask).astype(dtype)
        return chunk, halves + reference.half_sums(chunk), reference.strided_columns(chunk)

    small = replicated(devices, mesh)
    return jax.jit(make, out_shardings=(chunk_sharding(devices, mesh), small, small))
