"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process that owns the TPU drives the main path once, at the full width
of the repo's north-star configuration (packed Shamir k=5/t=2/n=8, 61-bit
field, dim 100 000), through the entry points a user calls:

- build leg (before JAX is imported): ``_sdanative`` from the committed C
  sources, in a child that needs no chip;
- device line: platform, device kind and count, jax / jaxlib / libtpu —
  anything but a TPU ends the run before a leg touches the device;
- protocol leg: one real round over loopback REST — ``participate_many``,
  eight clerks with one committee member dropped, ``reveal_aggregation`` —
  whose mask combine runs the compiled Pallas ChaCha kernel; the reveal
  equals the python-int sum exactly;
- fabric leg: every device kernel's bits against its reference
  (``kernel_parity``), then two chunks of seeded input folded through the
  entry points the benchmark's cells bind (``benchmark/traffic/*.json``),
  with the program's default draw, accumulated as that engine's traffic
  file says, through the matching host epilogue, revealed from 7 of 8
  clerks and compared with the exact column sums: sum-first 61-bit at dim
  100 000, then the per-participant engine at dim 10 000 in XLA's named
  formulation, through the entry the cell binds (the fused Pallas kernel on
  a TPU) and through the kernel's explicit entry, whose accumulators must
  all be the same bits;
- sharded leg, with more than one chip: every fabric
  ``__graft_entry__.dryrun_multichip`` walks, plus the sum-first limb psum
  at dim 100 000, revealed and compared the same way, and the masked round
  over all the chips through ``fold_round(..., masking=, mesh=)``: every
  chip's own seeds, both kernels inside the ``shard_map`` body, the
  recipient's fold sharded too.

Rows are cut (never width); weights are seeded random. Any leg that raises
ends the run non-zero. Wall times are printed as information only. The
last stdout line is ``{"ok": true, "device": {...}}``.

The leg functions take their sizes as arguments so tier-1 can rehearse
them on the CPU at tiny sizes; run as a script, this accepts no CPU.
"""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import tempfile
import time

REPO = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(REPO))

#: the scheme every leg shares: packed Shamir, reconstruct from t+k = 7 of 8
K, T, N = 5, 2, 8


class SmokeFailure(RuntimeError):
    """A leg ran and its result is wrong."""


class NoAccelerator(RuntimeError):
    """JAX found no TPU and the caller did not ask for the CPU."""


def say(msg: str) -> None:
    print(msg, flush=True)


def build_leg() -> None:
    """Build the native extension from the committed sources and require
    it. A child process, so it runs before this process imports JAX; the
    compiler needs no chip, no network and no git."""
    if "jax" in sys.modules:
        raise SmokeFailure("build leg must run before JAX is imported")
    t0 = time.perf_counter()
    subprocess.run(
        [sys.executable, "setup.py", "-q", "build_ext", "--inplace"],
        cwd=REPO,
        check=True,
        timeout=600,
    )
    from sda_tpu import native

    if not native.available():
        raise SmokeFailure("_sdanative built but did not load")
    say(f"build leg ok: _sdanative loaded ({time.perf_counter() - t0:.1f} s)")


def device_line(*, allow_pinned_cpu: bool = False) -> dict:
    """First (and only) device touch of the process, in-process: a chip
    belongs to one process, so nothing here starts a child to look. Prints
    what JAX reports.

    A TPU is the only device the smoke may run on. The CPU is accepted only
    where the caller allows it AND ``JAX_PLATFORMS`` names it explicitly
    (tier-1's rehearsal does) — a CPU that JAX fell back to on its own is a
    failure, not a device."""
    import importlib.metadata

    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    pinned_cpu = allow_pinned_cpu and "cpu" in [
        name.strip() for name in os.environ.get("JAX_PLATFORMS", "").lower().split(",")
    ]
    if device["platform"] != "tpu" and not pinned_cpu:
        raise NoAccelerator(
            f"JAX found no TPU (devices: {device}); the smoke runs on nothing "
            "else. For the CPU rehearsal see tests/test_chip_smoke.py."
        )

    def version(dist: str) -> str:
        try:
            return importlib.metadata.version(dist)
        except importlib.metadata.PackageNotFoundError:
            return "not installed"

    say(
        f"device: platform={device['platform']} kind={device['kind']!r} "
        f"count={device['count']} jax={version('jax')} "
        f"jaxlib={version('jaxlib')} libtpu={version('libtpu')}"
    )
    return device


def _scheme(min_modulus_bits: int):
    """Packed Shamir over the 61-bit field (60) or the 31-bit one (30)."""
    from sda_tpu.ops import find_packed_parameters
    from sda_tpu.protocol import PackedShamirSharing

    p, w2, w3 = find_packed_parameters(
        K, T, N, min_modulus_bits=min_modulus_bits, seed=0
    )
    return PackedShamirSharing(K, N, T, p, w2, w3)


def _chacha_expands(path: str) -> int:
    from sda_tpu import telemetry

    return sum(
        row["value"]
        for row in telemetry.snapshot(include_spans=0)["counters"]
        if row["name"] == "sda_crypto_chacha_expands_total"
        and row["labels"].get("path") == path
    )


def protocol_leg(*, dim: int = 100_000, participants: int = 64) -> None:
    """One full-width round through the product's entry points, over a
    live loopback REST server, ChaCha-masked so the reveal's mask combine
    crosses into the device plane."""
    import numpy as np

    from sda_tpu.client import SdaClient
    from sda_tpu.crypto import Keystore
    from sda_tpu.crypto.masking import ChaChaMasker
    from sda_tpu.ops.chacha_pallas import default_backend
    from sda_tpu.protocol import (
        Aggregation,
        AggregationId,
        ChaChaMasking,
        SodiumEncryptionScheme,
    )
    from sda_tpu.rest.client import SdaHttpClient
    from sda_tpu.rest.server import serve_background
    from sda_tpu.rest.tokenstore import TokenStore
    from sda_tpu.server import new_mem_server

    if participants * dim < ChaChaMasker.DEVICE_COMBINE_THRESHOLD:
        raise SmokeFailure(
            f"{participants} x {dim} is below the device-combine threshold: "
            "the reveal would never reach the device plane"
        )
    scheme = _scheme(60)
    p = scheme.prime_modulus
    vectors = np.random.default_rng(21).integers(0, p, size=(participants, dim))
    path = default_backend()
    expands_before = _chacha_expands(path)
    walls = {}

    with tempfile.TemporaryDirectory() as tmp, serve_background(
        new_mem_server()
    ) as url:
        service = SdaHttpClient(url, TokenStore(str(pathlib.Path(tmp) / "tokens")))

        def client(name: str) -> SdaClient:
            keystore = Keystore(str(pathlib.Path(tmp) / name))
            return SdaClient(SdaClient.new_agent(keystore), keystore, service)

        recipient = client("recipient")
        recipient.upload_agent()
        rkey = recipient.new_encryption_key()
        recipient.upload_encryption_key(rkey)
        clerks = [client(f"clerk{i}") for i in range(N)]
        for clerk in clerks:
            clerk.upload_agent()
            clerk.upload_encryption_key(clerk.new_encryption_key())
        agg = Aggregation(
            id=AggregationId.random(),
            title="chip-smoke",
            vector_dimension=dim,
            modulus=p,
            recipient=recipient.agent.id,
            recipient_key=rkey,
            masking_scheme=ChaChaMasking(modulus=p, dimension=dim, seed_bitsize=128),
            committee_sharing_scheme=scheme,
            recipient_encryption_scheme=SodiumEncryptionScheme(),
            committee_encryption_scheme=SodiumEncryptionScheme(),
        )
        recipient.upload_aggregation(agg)
        recipient.begin_aggregation(agg.id)

        t0 = time.perf_counter()
        phone = client("participant")
        phone.upload_agent()
        phone.participate_many([row.tolist() for row in vectors], agg.id)
        walls["participate"] = time.perf_counter() - t0
        recipient.end_aggregation(agg.id)

        # drop one ACTUAL committee member (the recipient is usually
        # elected too): Lagrange recovery from t+k = 7 of 8 must run
        committee = service.get_committee(recipient.agent, agg.id)
        members = [agent_id for agent_id, _ in committee.clerks_and_keys]
        by_id = {c.agent.id: c for c in [recipient, *clerks]}
        dropped = next(m for m in members if m != recipient.agent.id)
        t0 = time.perf_counter()
        for member in members:
            if member != dropped:
                by_id[member].run_chores(-1)
        walls["clerking"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        out = recipient.reveal_aggregation(agg.id)
        walls["reveal"] = time.perf_counter() - t0

    got = [int(v) for v in out.positive().values]
    want = [sum(int(v) for v in vectors[:, j]) % p for j in range(dim)]
    if got != want:
        raise SmokeFailure("protocol leg: reveal != python-int sum")
    expanded = _chacha_expands(path) - expands_before
    if expanded != participants:
        raise SmokeFailure(
            f"protocol leg: the {path} mask combine expanded {expanded} "
            f"seeds, expected {participants}"
        )
    say(
        f"protocol leg ok: {participants} participants x dim {dim}, "
        f"{p.bit_length()}-bit, {len(members) - 1} of {len(members)} clerks, "
        f"mask combine on {path}, reveal exact ("
        + ", ".join(f"{name} {s:.1f} s" for name, s in walls.items())
        + ")"
    )


def kernel_parity(
    *,
    seeds: int = 64,
    dim: int = 100_000,
    chunk: int = 2_000,
    limb_dim: int = 10_000,
) -> dict:
    """Bit-parity of every device kernel against its reference, at the main
    path's shapes by default: the reveal's ChaCha mask combine at ``seeds``
    x ``dim`` over the 61-bit field, and the per-participant share+combine
    at ``chunk`` x ``limb_dim``, 31-bit, K = 7.

    The backend decides what runs, nothing is caught: on a TPU the
    compiled Pallas kernels (a kernel that does not compile raises here);
    on the CPU the jnp twin that backend uses plus the kernel source
    under the Pallas interpreter. The first mismatch raises SmokeFailure.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sda_tpu.native import chacha_expand
    from sda_tpu.ops.chacha_pallas import combine_masks_device
    from sda_tpu.ops.modular import mod_sum_wide_np, positive
    from sda_tpu.parallel.engine import (
        make_plan,
        reconstruct,
        share_combine_limb,
        share_combine_limb_xla,
    )
    from sda_tpu.parallel.limb_pallas import share_combine_limb_pallas
    from sda_tpu.parallel.limbmatmul import limb_recombine_host

    on_tpu = jax.default_backend() == "tpu"
    out: dict = {"platform": jax.default_backend()}

    def same(name, got, want):
        if not np.array_equal(np.asarray(got), np.asarray(want)):
            raise SmokeFailure(f"{name}: device bits differ from the reference")
        out[name] = "ok"

    wide = _scheme(60)
    p61 = wide.prime_modulus

    # ChaCha: the recipient's mask combine, against the host expansion
    rng = np.random.default_rng(3)
    seed_rows = rng.integers(0, 2**32, size=(seeds, 4), dtype=np.uint32)
    want = mod_sum_wide_np(
        np.stack([chacha_expand(row, dim, p61) for row in seed_rows]), p61, axis=0
    )
    backends = ["pallas"] if on_tpu else ["jnp", "interpret"]
    out["chacha_backends"] = backends
    for backend in backends:
        got = combine_masks_device(seed_rows, dim, p61, backend=backend)
        same(f"chacha_{backend}", got, want)

    # the per-participant engine's fused kernel vs XLA's named formulation,
    # same key: on a TPU through the engine's own entry, which takes the
    # compiled kernel there; elsewhere the kernel's source on the interpreter
    narrow = _scheme(30)
    plan = make_plan(narrow, limb_dim)
    secrets = jnp.asarray(
        np.random.default_rng(4)
        .integers(0, plan.modulus, size=(chunk, limb_dim))
        .astype(np.int32)
    )
    key = jax.random.key(9)
    xla = jax.jit(lambda s, kk: share_combine_limb_xla(s, kk, plan))(secrets, key)
    if on_tpu:
        fused = jax.jit(lambda s, kk: share_combine_limb(s, kk, plan))(secrets, key)
    else:
        fused = jax.jit(
            lambda s, kk: share_combine_limb_pallas(s, kk, plan, interpret=True)
        )(secrets, key)
    same("limb", fused, xla)

    # wide field: limb accumulators -> exact host recombine -> reconstruct
    wide_dim = 25
    wplan = make_plan(wide, wide_dim)
    wsecrets = (
        p61 - np.random.default_rng(5).integers(1, 10_000, size=(32, wide_dim))
    ).astype(np.int64)
    acc = np.asarray(
        jax.jit(lambda s, kk: share_combine_limb(s, kk, wplan))(
            jnp.asarray(wsecrets), jax.random.key(2)
        )
    )
    clerk_sums = limb_recombine_host(acc, p61).T  # exact, host-side
    revealed = positive(
        np.asarray(reconstruct(jnp.asarray(clerk_sums), range(N), wide, wide_dim)),
        p61,
    )
    plain = np.array(
        [sum(int(v) for v in wsecrets[:, j]) % p61 for j in range(wide_dim)],
        dtype=np.int64,
    )
    same("wide61", revealed, plain)
    out["ok"] = True
    return out


def _check_reveal(leg: str, clerk_sums, scheme, secrets) -> None:
    """The reveal from 7 of the 8 clerks' sums (clerk 0 left out) must be the
    exact column sums of ``secrets`` (host int64, canonical) mod p."""
    import numpy as np

    from sda_tpu.ops.modular import positive
    from sda_tpu.ops.shamir import reconstruct_clerk_sums_host

    p, dim = scheme.prime_modulus, secrets.shape[1]
    survivors = list(range(1, 1 + scheme.reconstruction_threshold))
    out = reconstruct_clerk_sums_host(clerk_sums, survivors, scheme, dim)
    # exact column sums without python-int loops over the big tensor:
    # 32-bit halves summed in uint64, joined as python ints per column
    lo = (secrets & 0xFFFFFFFF).astype(np.uint64).sum(axis=0)
    hi = (secrets >> 32).astype(np.uint64).sum(axis=0)
    want = [((int(h) << 32) + int(l)) % p for h, l in zip(hi, lo)]
    if [int(v) for v in positive(np.asarray(out), p)] != want:
        raise SmokeFailure(
            f"{leg}: the reveal from {len(survivors)} of {scheme.share_count} "
            "clerks != the exact column sums"
        )


def fold_engine(engine: str, *, dim: int, chunk: int):
    """One engine of the fabric leg through the program's round driver
    (``sda_tpu.parallel.fold_round``), which pairs the entry with its
    accumulate rule and its epilogue as the cells' traffic files do:
    ``sumfirst`` as ``sumfirst-wide.json`` (61-bit, ``+``), ``participant`` as
    ``participant-narrow.json`` (31-bit, ``+`` then ``rem p``; the fused
    kernel where the step is compiled for a TPU), ``participant+xla`` the same
    round in XLA's named formulation, ``participant+pallas`` on the kernel's
    explicit entry. Two chunks of
    seeded host rows through the driver's feed with the program's default
    draw, the epilogue, the reveal compared. Returns the accumulator."""
    import functools

    import jax
    import numpy as np

    from sda_tpu.parallel import engine as engine_mod
    from sda_tpu.parallel import fold_round, limb_pallas, sumfirst

    t0 = time.perf_counter()
    # field width and chunk entry; the Pallas kernel never decides by itself
    # to interpret: its caller does
    fused = functools.partial(
        limb_pallas.share_combine_limb_pallas,
        interpret=jax.default_backend() != "tpu",
    )
    bits, entry = {
        "sumfirst": (60, sumfirst.value_limb_sums_chunk),
        "participant": (30, engine_mod.share_combine_limb),
        "participant+xla": (30, engine_mod.share_combine_limb_xla),
        "participant+pallas": (30, fused),
    }[engine]
    scheme = _scheme(bits)
    p = scheme.prime_modulus
    driver = fold_round(scheme, dim, entry, chunk)
    secrets = np.random.default_rng(23).integers(0, p, size=(2 * chunk, dim))
    blocks = [secrets[i * chunk : (i + 1) * chunk].astype(driver.input_dtype) for i in range(2)]
    acc = np.asarray(driver.fold_host_rows(blocks, jax.random.key(7), in_flight=2))
    _check_reveal(f"fabric leg, {engine}", driver.clerk_sums(acc), scheme, secrets)
    say(
        f"fabric leg ok: {engine} {p.bit_length()}-bit, {2 * chunk} rows x dim "
        f"{dim} in 2 chunks, default draw, reveal from 7 of 8 clerks exact "
        f"({time.perf_counter() - t0:.1f} s)"
    )
    return acc


def fabric_leg(
    *,
    dim: int = 100_000,
    chunk: int = 500,
    preset_dim: int = 10_000,
    preset_chunk: int = 2_000,
    seeds: int = 64,
) -> None:
    """Kernel parity at the main path's shapes, then each engine's round
    as the cells run it: sum-first at full width, per-participant at its
    preset width in XLA's named formulation, through the cell's entry and on
    the fused Pallas kernel by name, which must leave the same accumulator."""
    import numpy as np

    t0 = time.perf_counter()
    parity = kernel_parity(
        seeds=seeds, dim=dim, chunk=preset_chunk, limb_dim=preset_dim
    )
    say(f"fabric leg: kernel parity {parity} ({time.perf_counter() - t0:.1f} s)")
    fold_engine("sumfirst", dim=dim, chunk=chunk)
    xla = fold_engine("participant+xla", dim=preset_dim, chunk=preset_chunk)
    for engine in ("participant", "participant+pallas"):
        if not np.array_equal(fold_engine(engine, dim=preset_dim, chunk=preset_chunk), xla):
            raise SmokeFailure(
                f"fabric leg: {engine}'s accumulator differs from the XLA "
                "formulation's on the same key"
            )


def sharded_leg(*, dim: int = 100_000, rows_per_shard: int = 256) -> None:
    """On every local chip: the six fabrics of the multi-chip dry run, then
    the sum-first limb psum at full width, revealed and compared with the
    exact column sums."""
    import jax
    import numpy as np

    n_devices = len(jax.devices())
    if n_devices < 2:
        say(f"sharded leg skipped: {n_devices} device")
        return
    from __graft_entry__ import dryrun_multichip

    import jax.numpy as jnp

    from sda_tpu.parallel import (
        make_mesh,
        make_plan,
        shard_participants,
        sharded_value_limb_sums,
    )
    from sda_tpu.parallel.sumfirst import clerk_sums_from_limb_acc

    t0 = time.perf_counter()
    dryrun_multichip(n_devices)

    d_size = 2 if n_devices % 2 == 0 else 1
    mesh = make_mesh(p_size=n_devices // d_size, d_size=d_size)
    scheme = _scheme(60)
    plan = make_plan(scheme, dim)
    rows = rows_per_shard * mesh.shape["p"]
    secrets = np.random.default_rng(22).integers(
        0, scheme.prime_modulus, size=(rows, dim)
    )
    acc = np.asarray(
        sharded_value_limb_sums(plan, mesh)(
            shard_participants(jnp.asarray(secrets), mesh), jax.random.key(5)
        )
    )
    clerk_sums, _ = clerk_sums_from_limb_acc(acc, plan)
    _check_reveal("sharded leg", clerk_sums, scheme, secrets)
    masked_round_over(n_devices, scheme, secrets[: rows_per_shard // 2 * n_devices])
    say(
        f"sharded leg ok: {n_devices} devices, six dry-run fabrics + sum-first "
        f"limb psum over p={mesh.shape['p']} d={d_size} at dim {dim}, {rows} "
        f"rows, exact + the masked round over p={n_devices} "
        f"({time.perf_counter() - t0:.1f} s)"
    )


def masked_round_over(n_devices: int, scheme, secrets) -> None:
    """The masked round on however many chips there are, through the round
    driver with the deployment's mesh (``fold_round(..., masking=, mesh=)``):
    rows over ``p``, every chip masking its own under seeds of its own (both
    kernels inside the ``shard_map`` body on a TPU), the recipient's sharded
    fold over the seeds where they lie. The unmasked aggregate must be the
    exact column sums, the clerks' sums must carry masks, and no two rows may
    share a seed."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sda_tpu.parallel import fold_round, make_mesh, shard_participants, sumfirst
    from sda_tpu.protocol import ChaChaMasking

    p, (rows, dim) = scheme.prime_modulus, secrets.shape
    mesh = make_mesh(p_size=n_devices, d_size=1)
    driver = fold_round(
        scheme, dim, sumfirst.value_limb_sums_chunk, rows,
        masking=ChaChaMasking(p, dim, 128), mesh=mesh,
    )
    acc, seeds, counts = driver.fold_chunks(
        [shard_participants(jnp.asarray(secrets), mesh)], jax.random.key(6)
    )
    if driver.short_windows(counts):
        raise SmokeFailure("sharded leg, masked: a rejection window came short (~1e-9 a row)")
    clerk_sums = driver.clerk_sums(acc)
    masked = driver.reveal(clerk_sums, range(1, 1 + scheme.reconstruction_threshold))
    seed_rows = np.concatenate([np.asarray(s) for s in seeds])
    if len({tuple(row) for row in seed_rows}) != rows:
        raise SmokeFailure("sharded leg, masked: two rows share a seed")
    lo = (secrets & 0xFFFFFFFF).astype(np.uint64).sum(axis=0)
    hi = (secrets >> 32).astype(np.uint64).sum(axis=0)
    want = [((int(h) << 32) + int(l)) % p for h, l in zip(hi, lo)]
    if [int(v) for v in masked] == want:
        raise SmokeFailure("sharded leg, masked: the clerks' sums carry no mask")
    if [int(v) for v in driver.unmask(masked, seeds)] != want:
        raise SmokeFailure(
            "sharded leg, masked: the unmasked aggregate != the exact column sums"
        )


def main() -> int:
    t0 = time.perf_counter()
    build_leg()
    try:
        device = device_line()
    except NoAccelerator as exc:
        print(f"chip_smoke: {exc}", file=sys.stderr, flush=True)
        return 2
    protocol_leg()
    fabric_leg()
    sharded_leg()
    say(f"chip_smoke: all legs passed in {time.perf_counter() - t0:.0f} s")
    say(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
