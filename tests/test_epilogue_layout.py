"""The device epilogue and reveal (``sda_tpu/parallel/epilogue.py``) as the
chip's compiler makes them, for a described v5e:2x2 (compile only: nothing
runs, no time is taken). Their 61-bit field arithmetic is bound by the vector
unit, so it has to run on whole (8, 128) tiles: no fusion that multiplies may
take or give a uint32 array of a block's size tiled one or two rows of 128 to
a register (``T(1,128)``, ``T(2,128)``), which leaves most of every register's
sublanes idle. The word accumulator itself comes tiled ``T(2,128)`` from the
chunk step, so its slices may be read in that tiling.

In the manner of ``tests/test_sumfirst_compiled.py``: the topology is
described inside a module-scoped fixture, which skips where it cannot be, and
the compile cache is off around the compiles.
"""

import math
import os
import re

import pytest

#: batches a block: two blocks of 2¹³ batches, at k = 5
BLOCK = 1 << 13
DIM = 5 * 2 * BLOCK

INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([a-z][\w\-]*)\((.*)$")
COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) ")
THIN = re.compile(r"u32\[([\d,]+)\]\{[^}]*:T\([12],128\)")


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # whatever keeps the TPU's compiler from loading
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compiled(one_chip):
    """``{program: compiled text}`` of the device epilogue and reveal at a
    61-bit plan of DIM, in blocks of BLOCK batches."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from sda_tpu.ops import find_packed_parameters
    from sda_tpu.ops.jaxcfg import ensure_x64
    from sda_tpu.parallel import epilogue
    from sda_tpu.parallel.engine import make_plan
    from sda_tpu.protocol import PackedShamirSharing

    ensure_x64()
    p, w2, w3 = find_packed_parameters(5, 2, 8, min_modulus_bits=60, seed=0)
    plan = make_plan(PackedShamirSharing(5, 8, 2, p, w2, w3), DIM)
    B, K, n = plan.n_batches, plan.input_size + plan.rand_size, plan.share_count
    assert B == 2 * BLOCK

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(epilogue, "BLOCK_BATCHES", BLOCK)
            sums = epilogue.clerk_sums_program(plan).lower(shape((2, 2, B * K), jnp.uint32))
            reveal = epilogue.reveal_program(plan).lower(
                shape((n, B), jnp.int64), shape((7,), jnp.int32), shape((4, 5, 7), jnp.uint32)
            )
            # the aggregate as one array of its words, low and high interleaved
            assert (reveal.out_info.shape, reveal.out_info.dtype) == ((2 * DIM,), jnp.uint32)
            return {
                "device_clerk_sums": sums.compile().as_text(),
                "device_reveal": reveal.compile().as_text(),
            }
    finally:
        jax.config.update("jax_enable_compilation_cache", before)


def multiplying_fusions(text):
    """``[(name, result type, [operand types])]`` of every fusion whose called
    computation, or one it calls, holds a ``multiply``."""
    bodies, types, current = {}, {}, None
    for line in text.splitlines():
        head = COMPUTATION.match(line)
        if head and line.rstrip().endswith("{"):
            current = bodies.setdefault(head.group(1), [])
            continue
        ins = INSTRUCTION.match(line)
        if ins and current is not None:
            current.append(ins)
            types[ins.group(1)] = ins.group(2)

    def multiplies(name, seen=()):
        body = bodies.get(name, [])
        if any(ins.group(3) == "multiply" for ins in body):
            return True
        called = {c for ins in body for c in re.findall(r"calls=%?([\w.\-]+)", ins.group(4))}
        return any(multiplies(c, seen + (name,)) for c in called if c not in seen)

    found = []
    for body in bodies.values():
        for ins in body:
            if ins.group(3) != "fusion":
                continue
            callee = re.search(r"calls=%?([\w.\-]+)", ins.group(4)).group(1)
            if multiplies(callee):
                operands = re.findall(r"%([\w.\-]+)", ins.group(4).split("), kind=")[0])
                found.append((ins.group(1), ins.group(2), [types.get(o, "") for o in operands]))
    return found


def thin(type_text, word_acc=False):
    """The uint32 arrays of 2¹³ values or more in ``type_text`` tiled one or
    two rows of 128 to a register; with ``word_acc``, slices of the ``(L, 2,
    N)`` word accumulator left out."""
    out = []
    for dims in THIN.findall(type_text):
        dims = [int(d) for d in dims.split(",")]
        if math.prod(dims) < BLOCK:
            continue
        if word_acc and len(dims) == 3 and dims[1] == 2:
            continue
        out.append(dims)
    return out


@pytest.mark.parametrize("program", ["device_clerk_sums", "device_reveal"])
def test_the_field_arithmetic_runs_on_whole_tiles(program, compiled):
    fusions = multiplying_fusions(compiled[program])
    # the products are there, a block's batches at a time
    sizes = [
        math.prod(int(d) for d in dims.split(","))
        for _name, result, _operands in fusions
        for dims in re.findall(r"u32\[([\d,]+)\]", result)
    ]
    assert max(sizes, default=0) >= BLOCK, program
    offenders = {
        name: thin(result) + [dims for op in operands for dims in thin(op, word_acc=True)]
        for name, result, operands in fusions
    }
    assert not {name: dims for name, dims in offenders.items() if dims}, program
