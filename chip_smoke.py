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
- fabric leg: the kernel parity routine and the loop ``python bench.py``
  runs (``bench.run_fabric``): sum-first 61-bit at dim 100 000, then the
  per-participant engine at its preset width on the XLA int8-limb path and
  the fused Pallas kernel;
- sharded leg, with more than one chip: every fabric
  ``__graft_entry__.dryrun_multichip`` walks, plus the sum-first limb psum
  at dim 100 000.

Rows are cut (never width); weights are seeded random. Any leg that raises
ends the run non-zero. Wall times are printed as information only. The
last stdout line is ``{"ok": true, "device": {...}}``.

The leg functions take their sizes as arguments so tier-1 can rehearse
them on the CPU at tiny sizes; run as a script, this accepts no CPU.
"""

from __future__ import annotations

import json
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
    """Acquire the device in this process and print what JAX reports."""
    import importlib.metadata

    import bench

    device = bench.acquire_device(allow_pinned_cpu=allow_pinned_cpu)

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


def _wide_scheme():
    from sda_tpu.ops import find_packed_parameters
    from sda_tpu.protocol import PackedShamirSharing

    p, w2, w3 = find_packed_parameters(K, T, N, min_modulus_bits=60, seed=0)
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
    scheme = _wide_scheme()
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


def fabric_leg(
    *,
    dim: int = 100_000,
    chunk: int = 500,
    participants: int = 100_000,
    preset_dim: int = 10_000,
    preset_chunk: int = 2_000,
    preset_participants: int = 8_000,
    seeds: int = 64,
) -> None:
    """Kernel parity at the main path's shapes, then the loop bench.py
    runs: sum-first at full width, per-participant at its preset width on
    both the XLA limb path and the fused Pallas kernel (the parity routine
    holds the two bit-identical on the same key)."""
    import bench

    t0 = time.perf_counter()
    parity = bench.kernel_parity(
        seeds=seeds, dim=dim, chunk=preset_chunk, limb_dim=preset_dim
    )
    say(f"fabric leg: kernel parity {parity} ({time.perf_counter() - t0:.1f} s)")

    def sized(rows: int, width: int, per_chunk: int) -> list[str]:
        # two segments, so one is steady; parity already ran above
        return ["--participants", str(rows), "--dim", str(width),
                "--chunk", str(per_chunk), "--segments", "2", "--no-parity"]

    preset = ["--engine", "participant",
              *sized(preset_participants, preset_dim, preset_chunk)]
    runs = {
        "sumfirst": sized(participants, dim, chunk),
        "participant": preset,
        "participant+pallas": [*preset, "--pallas"],
    }
    for engine, argv in runs.items():
        t0 = time.perf_counter()
        line = bench.run_fabric(bench.parse_args(argv))  # raises unless verified
        if line["engine"] != engine or line.get("partial") or line.get("includes_compile"):
            raise SmokeFailure(f"fabric leg: {engine} did not run whole: {line}")
        say(
            f"fabric leg ok: {engine} {line['modulus_bits']}-bit, "
            f"{line['participants']} rows x dim {line['dim']}, chunk "
            f"{line['chunk']}, reconstruct exact ({time.perf_counter() - t0:.1f} s, "
            f"compile + first segment {line['compile_and_first_s']:.1f} s)"
        )


def sharded_leg(*, dim: int = 100_000, rows_per_shard: int = 256) -> None:
    """On every local chip: the six fabrics of the multi-chip dry run, then
    the sum-first limb psum at full width, checked against exact sums."""
    import jax
    import numpy as np

    n_devices = len(jax.devices())
    if n_devices < 2:
        say(f"sharded leg skipped: {n_devices} device")
        return
    from __graft_entry__ import dryrun_multichip

    import jax.numpy as jnp

    from sda_tpu.ops.modular import positive
    from sda_tpu.parallel import (
        make_mesh,
        make_plan,
        shard_participants,
        sharded_value_limb_sums,
    )
    from sda_tpu.parallel.sumfirst import (
        clerk_sums_from_limb_acc,
        reconstruct_from_clerk_sums,
    )

    t0 = time.perf_counter()
    dryrun_multichip(n_devices)

    d_size = 2 if n_devices % 2 == 0 else 1
    mesh = make_mesh(p_size=n_devices // d_size, d_size=d_size)
    scheme = _wide_scheme()
    p = scheme.prime_modulus
    plan = make_plan(scheme, dim)
    rows = rows_per_shard * mesh.shape["p"]
    secrets = np.random.default_rng(22).integers(0, p, size=(rows, dim))
    acc = np.asarray(
        sharded_value_limb_sums(plan, mesh)(
            shard_participants(jnp.asarray(secrets), mesh), jax.random.key(5)
        )
    )
    clerk_sums, _ = clerk_sums_from_limb_acc(acc, plan)
    survivors = list(range(1, 1 + scheme.reconstruction_threshold))
    out = reconstruct_from_clerk_sums(clerk_sums, survivors, scheme, dim)
    # exact column sums without python-int loops over the big tensor:
    # 32-bit halves summed in uint64, joined as python ints per column
    lo = (secrets & 0xFFFFFFFF).astype(np.uint64).sum(axis=0)
    hi = (secrets >> 32).astype(np.uint64).sum(axis=0)
    want = [(int(h) << 32) + int(l) for h, l in zip(hi, lo)]
    if [int(v) for v in positive(np.asarray(out), p)] != [w % p for w in want]:
        raise SmokeFailure("sharded leg: sum-first limb psum != exact sums")
    say(
        f"sharded leg ok: {n_devices} devices, six dry-run fabrics + sum-first "
        f"limb psum over p={mesh.shape['p']} d={d_size} at dim {dim}, {rows} "
        f"rows, exact ({time.perf_counter() - t0:.1f} s)"
    )


def main() -> int:
    t0 = time.perf_counter()
    build_leg()
    import bench

    try:
        device = device_line()
    except bench.NoAccelerator as exc:
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
