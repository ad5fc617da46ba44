"""The round of the four fabric cells: packed Shamir, no masking, a resident
input folded chunk by chunk.

The clock runs from key to comparison: fresh share key -> the engine's chunk
step over every chunk of the resident input, with the program's default share
randomness -> ``block_until_ready`` and transfer of the accumulator -> host
epilogue to clerk sums -> drop the configuration's clerks -> reconstruct from
exactly ``reconstruction_threshold`` survivors -> compare the whole aggregate
with the plain reference.

Its traffic file names, by dotted path, the engine entry and its calling
convention (``engine``, ``engine_call``), the host epilogue and its
(``epilogue``, ``epilogue_call``), ``reconstruct``, and how the step
accumulates (``accumulate``: ``sum`` or ``sum_mod_p``). The interface is
described in :mod:`benchmark.rounds`.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from benchmark import reference
from benchmark import traffic as traffic_mod
from benchmark.harness import HarnessError

#: the spans a round opens inside the harness's ``round``
span_names = ("dispatch", "fold", "fetch", "epilogue", "check")

#: what the round reads of its traffic file beyond the generator's fields
TRAFFIC_KEYS = ("engine", "engine_call", "epilogue", "epilogue_call", "reconstruct", "accumulate")


@dataclasses.dataclass(frozen=True)
class Program:
    """What a cell runs, built from its files: the program's plan and scheme,
    the jitted chunk step, the host epilogue and reconstruct. Holds no array,
    so the compile rehearsal builds it for described devices too."""

    scheme: object
    plan: object
    modulus: int
    chunk_fn: object  # fn(secrets, key) -> accumulator, one chunk
    step: object  # jitted fn(acc, chunk, key, i) -> acc
    epilogue: object  # fn(acc_host) -> (n, B) clerk sums
    reconstruct: object
    survivors: list  # exactly reconstruction_threshold surviving clerks
    second_subset: list  # warm-up's second subset: another clerk left out


def build_program(cell, mesh) -> Program:
    import jax

    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp
    from jax import lax

    from sda_tpu.ops import find_packed_parameters
    from sda_tpu.parallel.engine import make_plan
    from sda_tpu.protocol import PackedShamirSharing

    spec = cell.config["scheme"]
    if spec["kind"] != "packed_shamir":
        raise HarnessError(f"unknown scheme kind {spec['kind']!r}")
    k, t, n = spec["secret_count"], spec["privacy_threshold"], spec["share_count"]
    p, w2, w3 = find_packed_parameters(
        k, t, n, min_modulus_bits=spec["min_modulus_bits"], seed=spec["parameter_seed"]
    )
    scheme = PackedShamirSharing(k, n, t, p, w2, w3)
    modulus = int(p)
    plan = make_plan(scheme, cell.dim)
    threshold = scheme.reconstruction_threshold
    stated = cell.config["guarantees"]
    if stated["reconstruction_threshold"] != threshold or stated["privacy_threshold"] != t:
        raise HarnessError("the configuration's stated thresholds are not the scheme's")
    dropped = set(cell.config["dropped_clerks"])
    alive = [i for i in range(n) if i not in dropped]
    if len(alive) < threshold:
        raise HarnessError("fewer clerks survive than reconstruction needs")
    survivors = alive[:threshold]

    tr = cell.traffic
    traffic_mod.require(tr.params, TRAFFIC_KEYS, tr.name)
    calls = tr.params
    if calls["accumulate"] not in ("sum", "sum_mod_p"):
        raise HarnessError(f"{tr.name}: accumulate is 'sum' or 'sum_mod_p'")
    chunk_fn = traffic_mod.resolve(calls["engine_call"])(
        traffic_mod.resolve(calls["engine"]), plan, mesh
    )
    accumulate_mod_p = calls["accumulate"] == "sum_mod_p"

    def step(acc, chunk, key, i):
        # one chunk step: a chunk of the resident input, the round's key with
        # the step's number folded in, the program's default share randomness
        out = chunk_fn(chunk, jax.random.fold_in(key, i))
        acc = acc + out
        if accumulate_mod_p:
            acc = lax.rem(acc, jnp.int64(modulus))
        return acc

    return Program(
        scheme=scheme,
        plan=plan,
        modulus=modulus,
        chunk_fn=chunk_fn,
        step=jax.jit(step),
        epilogue=traffic_mod.resolve(calls["epilogue_call"])(
            traffic_mod.resolve(calls["epilogue"]), plan
        ),
        reconstruct=traffic_mod.resolve(calls["reconstruct"]),
        survivors=survivors,
        second_subset=[i for i in range(n) if i != survivors[-1]][:threshold],
    )


class Session:
    """One cell set up on its devices: input and reference resident, the
    chunk step ready, to run rounds."""

    def __init__(self, cell, seed: int, devices, stages=None):
        """``stages``, if given, is filled with the seconds each part of
        set-up took, for the run's record."""
        import jax
        import jax.numpy as jnp

        clock = time.perf_counter()
        stages = {} if stages is None else stages

        def stage(name):
            nonlocal clock
            now = time.perf_counter()
            stages[name] = now - clock
            clock = now

        if len(devices) < cell.chips:
            raise HarnessError(
                f"cell {cell.name!r} needs {cell.chips} devices, got {len(devices)}"
            )
        self.cell = cell
        self.devices = list(devices[: cell.chips])
        tr = cell.traffic
        self.mesh = traffic_mod.make_mesh(tr, self.devices)
        self.program = program = build_program(cell, self.mesh)
        self.plan, self.modulus = program.plan, program.modulus
        self.warmup_subsets = [program.second_subset]
        modulus = program.modulus
        steps = tr.steps_per_pass

        # everything a step takes besides its chunk sits on every chip before
        # the window, so that a step moves nothing between chips but its psum
        everywhere = traffic_mod.replicated(self.devices, self.mesh)
        self.step_index = [
            jax.device_put(jnp.int32(i), everywhere) for i in range(steps * tr.passes)
        ]
        self.steps_per_round = len(self.step_index)
        self.fold_in = jax.jit(jax.random.fold_in, out_shardings=everywhere)
        # the input and the reference's sums of it, made on the device from
        # the seed by one program, chunk by chunk
        stage("program")
        make = traffic_mod.chunk_maker(tr, cell.dim, modulus, self.devices, self.mesh)
        seed_key = jax.random.key(seed)
        input_key = self.fold_in(seed_key, 0)
        self.share_key = self.fold_in(seed_key, 1)
        half_sums = jax.device_put(jnp.zeros((2, cell.dim), jnp.int64), everywhere)
        self.chunks, columns = [], []
        for i in self.step_index[:steps]:
            chunk, half_sums, strided = make(input_key, i, half_sums)
            self.chunks.append(chunk)
            columns.append(strided)
        half_sums, columns = np.asarray(half_sums), [np.asarray(c) for c in columns]
        stage("input_on_device")
        self.want = reference.aggregate(
            half_sums, np.concatenate(columns), modulus, tr.passes, tr.rows
        )
        stage("reference_on_host")
        acc_shape = jax.eval_shape(program.chunk_fn, self.chunks[0], self.share_key)
        self.zero_acc = jax.device_put(jnp.zeros(acc_shape.shape, jnp.int64), everywhere)
        self.chunk_bytes = int(self.chunks[0].nbytes)
        self.acc_bytes = int(self.zero_acc.nbytes)

    def run_round(self, index: int, spans, subsets=None):
        """One round. Returns ``(matched, clerk_sums)``; ``subsets`` (warm-up
        only) are further clerk subsets that must reveal the same."""
        with spans("round", index):
            key = self.fold_in(self.share_key, index)
            with spans("dispatch", index):
                acc = self.zero_acc
                for i, step_number in enumerate(self.step_index):
                    chunk = self.chunks[i % len(self.chunks)]  # passes wrap
                    acc = self.program.step(acc, chunk, key, step_number)
            with spans("fold", index):
                acc.block_until_ready()
            with spans("fetch", index):
                acc_host = np.asarray(acc)
            with spans("epilogue", index):
                clerk_sums = np.asarray(self.program.epilogue(acc_host))
                got = self._reveal(clerk_sums, self.program.survivors)
            with spans("check", index):
                matched = bool(np.array_equal(got, self.want))
        for subset in subsets or ():
            matched = matched and bool(
                np.array_equal(self._reveal(clerk_sums, subset), self.want)
            )
        return matched, clerk_sums

    def _reveal(self, clerk_sums, subset):
        out = self.program.reconstruct(clerk_sums, subset, self.program.scheme, self.cell.dim)
        return np.mod(np.asarray(out).astype(np.int64), self.modulus)

    def memory_peak_bytes(self) -> int:
        """The peak on the fullest of the cell's chips (0 where the backend
        reports none, as the CPU does)."""
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in self.devices]
        return int(max(peaks))


def _described(cell, devices):
    """The cell's program, its input's maker and the shapes both take, placed
    on ``devices`` (attached or only described). Holds no array."""
    import jax

    tr = cell.traffic
    devices = list(devices[: cell.chips])
    mesh = traffic_mod.make_mesh(tr, devices)
    program = build_program(cell, mesh)
    small = traffic_mod.replicated(devices, mesh)

    def placed(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=small)

    key = jax.eval_shape(lambda: jax.random.key(0))
    chunk = jax.ShapeDtypeStruct(
        (tr.chunk, cell.dim), traffic_mod.input_dtype(program.modulus),
        sharding=traffic_mod.chunk_sharding(devices, mesh),
    )
    acc = jax.eval_shape(program.chunk_fn, chunk, key)
    key, index = placed(key.shape, key.dtype), placed((), "int32")
    maker = traffic_mod.chunk_maker(tr, cell.dim, program.modulus, devices, mesh)
    return {
        "step": (program.step, (placed(acc.shape, "int64"), chunk, key, index)),
        "input": (maker, (key, index, placed((2, cell.dim), "int64"))),
    }


def steps(cell, devices) -> list:
    """``[(jitted, example arguments)]``: the one program a round runs on the
    device inside the window, the chunk step."""
    return [_described(cell, devices)["step"]]


def input_maker(cell, devices) -> tuple:
    """``(jitted, example arguments)`` of the program that makes one chunk of
    the resident input in set-up, for the compile rehearsal."""
    return _described(cell, devices)["input"]
