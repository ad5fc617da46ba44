"""Run the BASELINE.md target-config ladder end-to-end and record wall
clocks (VERDICT r2 #4).

Configs (BASELINE.md "Target configs"):

1. simple-cli parity — additive 3-way, dim=10, mod 433, the walkthrough's
   3 participant vectors; expected output ``0 2 2 4 4 6 6 8 8 10``.
2. additive 3-way, dim=100K, 1K participants, 32-bit prime — full
   protocol with real sodium-sealed transport through the mem server.
3. packed Shamir t=2, n=5 (k=2), dim=10K, 10K participants — full
   protocol, sealed transport; the in-context seal/open rates ride along.
4. packed Shamir with clerk dropout, dim=50K, 100K participants — the
   aggregation fabric path (sum-first streaming on the ambient JAX
   backend), one clerk row dropped, Lagrange recovery, verified against
   an independent plaintext sum. The per-phone protocol plane at this
   scale is the TPU fabric's job (SURVEY §2.3), not a 1-core host loop —
   the host-protocol configs above already witness the transport plane.
5. the north star (1M x 100K, 61-bit, TPU) — its configuration is the
   benchmark's (``python benchmark/run.py --workload c5-sumfirst``,
   PERF.md); recorded here as a pointer, not re-run.

Plus ``sumfirst-1m``: a genuine 1M-participant sum-first run (dim 1024,
61-bit) exercising the documented int64 exactness bound
(parallel/sumfirst.py MAX_PARTICIPANTS) on host, bit-verified.

Usage: python scripts/baseline_ladder.py [--out FILE] [--quick] [--device]
``--quick`` divides participant counts by 100 (CI smoke; recorded as
such). ``--device`` (VERDICT r4 #4) runs configs 2-4 through the TPU
aggregation-fabric engines on the *ambient* JAX backend instead of the
host protocol loop — the math plane each config's scheme defines
(additive / basic-Shamir / packed-Shamir share arithmetic on device),
labeled as such; sealed transport stays priced by the host rows. Writes
one JSON artifact and prints it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "tests"))

# host ladder: force the CPU backend BEFORE any jax import (this artifact
# is a host measurement and must never take a chip). SDA_LADDER_PLATFORM
# overrides; --device keeps the ambient backend (the chip on a machine
# that holds one, the CPU in the rehearsal) — checked here, before
# argparse, because the jax platform must be pinned pre-import.
if "--device" not in sys.argv:
    os.environ["JAX_PLATFORMS"] = os.environ.get("SDA_LADDER_PLATFORM", "cpu")
elif "SDA_LADDER_PLATFORM" in os.environ:
    os.environ["JAX_PLATFORMS"] = os.environ["SDA_LADDER_PLATFORM"]

import numpy as np

#: per-config wall-clock budget (seconds) for the --device fabric loops,
#: checked COOPERATIVELY between chunks: a slow-but-healthy chip stops
#: early with a verified partial result instead of being killed by an
#: external timeout mid-device-op. None = unlimited (host mode keeps its
#: historical semantics).
_DEVICE_BUDGET: float | None = None


def _budget_spent(t0: float, done: int) -> bool:
    """True when the device budget is spent and at least one chunk landed
    (a partial-but-verified result beats an unverifiable empty one)."""
    return (
        _DEVICE_BUDGET is not None
        and done > 0
        and time.perf_counter() - t0 > _DEVICE_BUDGET
    )


def _client(tmp, name, service):
    from sda_fixtures import new_client

    return new_client(Path(tmp) / name, service)


def _setup_round(tmp, service, scheme, masking, dim, modulus, n_keyed):
    """Recipient + keyed clerks + open aggregation; returns (recipient,
    committee member clients by id, aggregation)."""
    from sda_tpu.protocol import (
        Aggregation,
        AggregationId,
        SodiumEncryptionScheme,
    )

    recipient = _client(tmp, "recipient", service)
    rkey = recipient.new_encryption_key()
    recipient.upload_agent()
    recipient.upload_encryption_key(rkey)
    clerks = [_client(tmp, f"clerk{i}", service) for i in range(n_keyed)]
    for c in clerks:
        c.upload_agent()
        c.upload_encryption_key(c.new_encryption_key())
    agg = Aggregation(
        id=AggregationId.random(),
        title="ladder",
        vector_dimension=dim,
        modulus=modulus,
        recipient=recipient.agent.id,
        recipient_key=rkey,
        masking_scheme=masking,
        committee_sharing_scheme=scheme,
        recipient_encryption_scheme=SodiumEncryptionScheme(),
        committee_encryption_scheme=SodiumEncryptionScheme(),
    )
    recipient.upload_aggregation(agg)
    recipient.begin_aggregation(agg.id)
    return recipient, clerks, agg


def _run_protocol_round(tmp, service, scheme, masking, dim, modulus,
                        n_keyed, vectors, drop_one=False):
    """Full protocol round; returns phase wall clocks + verified flag."""
    recipient, clerks, agg = _setup_round(
        tmp, service, scheme, masking, dim, modulus, n_keyed
    )
    phases = {}

    t0 = time.perf_counter()
    # one reusable participant identity: the ladder measures pipeline
    # throughput, not keystore setup; participation ids are fresh per call.
    # The whole cohort rides the batched path — one shared-ephemeral seal
    # per chunk and the bulk submit route, not a per-row round-trip.
    part = _client(tmp, "part", service)
    part.upload_agent()
    part.participate_many([row.tolist() for row in vectors], agg.id)
    phases["participate_s"] = round(time.perf_counter() - t0, 3)

    t0 = time.perf_counter()
    recipient.end_aggregation(agg.id)
    phases["snapshot_s"] = round(time.perf_counter() - t0, 3)

    committee = service.get_committee(recipient.agent, agg.id)
    member_ids = [c for c, _ in committee.clerks_and_keys]
    by_id = {c.agent.id: c for c in [recipient] + clerks}
    dropped = None
    if drop_one:
        dropped = next(c for c in member_ids if c != recipient.agent.id)
    t0 = time.perf_counter()
    for cid in member_ids:
        if cid != dropped:
            by_id[cid].run_chores(-1)
    phases["clerking_s"] = round(time.perf_counter() - t0, 3)

    t0 = time.perf_counter()
    out = recipient.reveal_aggregation(agg.id)
    phases["reveal_s"] = round(time.perf_counter() - t0, 3)

    got = np.asarray(out.positive().values)
    want = vectors.sum(axis=0) % modulus
    phases["verified"] = bool(np.array_equal(got, want))
    phases["dropped_member"] = dropped is not None
    return phases, got


def config1() -> dict:
    """simple-cli-example.sh parity: expected 0 2 2 4 4 6 6 8 8 10."""
    from sda_tpu.protocol import AdditiveSharing, NoMasking
    from sda_tpu.server import new_mem_server

    vectors = np.array([
        [0, 1, 2, 3, 4, 5, 6, 7, 8, 9],
        [0, 0, 0, 0, 0, 0, 0, 0, 0, 0],
        [0, 1, 0, 1, 0, 1, 0, 1, 0, 1],
    ])
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory() as tmp:
        phases, got = _run_protocol_round(
            tmp, new_mem_server(), AdditiveSharing(share_count=3, modulus=433),
            NoMasking(), 10, 433, 3, vectors,
        )
    expected = [0, 2, 2, 4, 4, 6, 6, 8, 8, 10]
    return {
        "config": "1: simple-cli parity (additive-3, dim 10, mod 433)",
        "wall_s": round(time.perf_counter() - t0, 3),
        "output_matches_walkthrough": got.tolist() == expected,
        **phases,
    }


def config2(n_participants: int) -> dict:
    """additive 3-way, dim=100K, 32-bit prime, sealed transport."""
    from sda_tpu.ops.params import is_prime
    from sda_tpu.protocol import AdditiveSharing, NoMasking
    from sda_tpu.server import new_mem_server

    dim, p = 100_000, 4294967291  # largest 32-bit prime
    assert is_prime(p)
    rng = np.random.default_rng(2)
    vectors = rng.integers(0, p, size=(n_participants, dim))
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory() as tmp:
        phases, _ = _run_protocol_round(
            tmp, new_mem_server(), AdditiveSharing(share_count=3, modulus=p),
            NoMasking(), dim, p, 3, vectors,
        )
    wall = time.perf_counter() - t0
    return {
        "config": f"2: additive-3, dim 100K, {n_participants} participants, 32-bit",
        "wall_s": round(wall, 3),
        "participants": n_participants,
        "participations_per_s": round(n_participants / phases["participate_s"], 2),
        "seals": n_participants * 3,
        **phases,
    }


def config3(n_participants: int) -> dict:
    """Shamir t=2 n=5, dim 10K, sealed transport. BASELINE's literal
    "t=2, n=5" committee cannot be NTT-packed (n+1 must be a power of 3,
    crypto.rs:146-153 radix structure), so this is BasicShamir — the
    k=1 Shamir variant with no radix constraints (schemes.py), the same
    trust shape the config names."""
    from sda_tpu.protocol import BasicShamirSharing, NoMasking
    from sda_tpu.server import new_mem_server

    t, n = 2, 5
    p = 1048583  # 21-bit prime (any prime works for BasicShamir)
    scheme = BasicShamirSharing(
        share_count=n, privacy_threshold=t, prime_modulus=p
    )
    dim = 10_000
    rng = np.random.default_rng(3)
    vectors = rng.integers(0, p, size=(n_participants, dim))
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory() as tmp:
        phases, _ = _run_protocol_round(
            tmp, new_mem_server(), scheme, NoMasking(), dim, p, n, vectors,
        )
    wall = time.perf_counter() - t0
    seals = n_participants * n
    return {
        "config": f"3: Shamir t=2 n=5 (basic; see docstring), dim 10K, "
                  f"{n_participants} participants, sealed transport",
        "wall_s": round(wall, 3),
        "participants": n_participants,
        "participations_per_s": round(n_participants / phases["participate_s"], 2),
        "seals": seals,
        "seals_per_s_in_context": round(seals / phases["participate_s"], 1),
        "seal_note": "the gap vs the 64 B seal microbench is NOT sealing: "
                     "the crypto rider's seals_per_s_4k/_40k size ladder "
                     "shows only ~25% drop at 40 KB payloads, and a "
                     "cProfile of this exact path puts ~70% of participate "
                     "wall in host share generation (ops/modular.modmatmul_np"
                     " + rem) and ~10% in sodium seals",
        **phases,
    }


def config4(n_participants: int) -> dict:
    """packed Shamir + dropout at 100K participants x 50K dims via the
    sum-first fabric (streamed), one clerk row corrupted+dropped."""
    from sda_tpu.ops import find_packed_parameters
    from sda_tpu.ops.jaxcfg import ensure_x64
    from sda_tpu.ops.modular import positive
    from sda_tpu.parallel.engine import make_plan
    from sda_tpu.parallel.sumfirst import (
        clerk_sums_from_limb_acc,
        reconstruct_from_clerk_sums,
        value_limb_sums_chunk,
    )
    from sda_tpu.protocol import PackedShamirSharing

    ensure_x64()
    import jax
    import jax.numpy as jnp

    k, t, n = 5, 2, 8
    p, w2, w3 = find_packed_parameters(k, t, n, min_modulus_bits=30, seed=0)
    scheme = PackedShamirSharing(k, n, t, p, w2, w3)
    dim = 50_000
    plan = make_plan(scheme, dim)
    chunk = 2_000
    rng = np.random.default_rng(4)
    key = jax.random.key(11)

    t0 = time.perf_counter()
    acc = None
    plain = np.zeros(dim, dtype=np.int64)
    done = 0
    while done < n_participants and not _budget_spent(t0, done):
        c = min(chunk, n_participants - done)
        secrets = rng.integers(0, p, size=(c, dim))
        key, sub = jax.random.split(key)
        a = np.asarray(value_limb_sums_chunk(jnp.asarray(secrets), sub, plan))
        acc = a if acc is None else acc + a
        # independent verification sums (int64 exact: values < 2^31,
        # 100K rows)
        plain += secrets.sum(axis=0)
        done += c
    clerk_sums, _ = clerk_sums_from_limb_acc(acc, plan)
    # dropout: corrupt clerk 3's row to prove it is never read, then
    # reconstruct from a strict subset (t+k of n)
    clerk_sums[3] = -7
    survivors = [i for i in range(n) if i != 3][: scheme.reconstruction_threshold]
    out = reconstruct_from_clerk_sums(clerk_sums, survivors, scheme, dim)
    wall = time.perf_counter() - t0
    got = positive(np.asarray(out), p)
    entry = {
        "config": f"4: packed Shamir dropout, dim 50K, {n_participants} "
                  "participants (sum-first fabric)",
        "backend": jax.devices()[0].platform,
        "wall_s": round(wall, 3),
        "participants": done,
        "elements": done * dim,
        "elements_per_s": round(done * dim / wall, 1),
        "verified": bool(np.array_equal(got, plain % p)),
        "dropped_clerk_row": 3,
    }
    if done < n_participants:
        entry["partial"] = True
    return entry


def config2_device(n_participants: int) -> dict:
    """Config 2's math plane on the device fabric: additive 3-way share
    generation (n-1 draws + closing share, additive.rs:42-48 semantics)
    for every participant on device, clerk-combine, additive
    reconstruction — streamed in chunks, verified against an independent
    host plaintext sum. The host config-2 row prices sealed transport;
    this row prices the share arithmetic itself at the same shape."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from sda_tpu.ops.jaxcfg import ensure_x64
    from sda_tpu.ops.modular import positive
    from sda_tpu.parallel.engine import (
        clerk_combine_mod,
        make_plan,
        reconstruct,
        share_participants,
    )
    from sda_tpu.protocol import AdditiveSharing

    ensure_x64()
    dim, p = 100_000, 4294967291  # same shape/modulus as the host row
    scheme = AdditiveSharing(share_count=3, modulus=p)
    plan = make_plan(scheme, dim)
    chunk = min(500, n_participants)
    rng = np.random.default_rng(12)
    key = jax.random.key(21)

    @jax.jit
    def step(acc, secrets, key):
        shares = share_participants(secrets, key, plan)  # (C, n, B)
        return lax.rem(acc + clerk_combine_mod(shares, p), jnp.int64(p))

    t0 = time.perf_counter()
    acc = jnp.zeros((scheme.share_count, dim), dtype=jnp.int64)
    plain = np.zeros(dim, dtype=np.int64)
    done = 0
    while done < n_participants and not _budget_spent(t0, done):
        c = min(chunk, n_participants - done)
        secrets = rng.integers(0, p, size=(c, dim))
        key, sub = jax.random.split(key)
        acc = step(acc, jnp.asarray(secrets), sub)
        plain += secrets.sum(axis=0)  # exact: n_participants * p < 2^63
        done += c
    got = positive(np.asarray(reconstruct(acc, range(3), scheme, dim)), p)
    wall = time.perf_counter() - t0
    out = {
        "config": f"2-device: additive-3 share fabric, dim 100K, "
                  f"{n_participants} participants, 32-bit",
        "plane": "device-fabric (share arithmetic; transport priced by the host row)",
        "backend": jax.devices()[0].platform,
        "wall_s": round(wall, 3),
        "participants": done,
        "elements": done * dim,
        "elements_per_s": round(done * dim / wall, 1),
        "verified": bool(np.array_equal(got, plain % p)),
    }
    if done < n_participants:
        out["partial"] = True
    return out


def config3_device(n_participants: int) -> dict:
    """Config 3's math plane on the device fabric: basic-Shamir t=2 n=5
    share matmuls via the fused int8-limb path (share_combine_limb), a
    streamed participant reduction, device Lagrange reconstruction from
    a strict 3-of-5 survivor subset (the dropout bound the trust shape
    promises). Verified against an independent host plaintext sum."""
    import jax
    import jax.numpy as jnp

    from sda_tpu.ops.jaxcfg import ensure_x64
    from sda_tpu.ops.modular import positive
    from sda_tpu.parallel.engine import (
        make_plan,
        reconstruct,
        share_combine_limb,
    )
    from sda_tpu.parallel.limbmatmul import limb_recombine
    from sda_tpu.protocol import BasicShamirSharing

    ensure_x64()
    t, n = 2, 5
    p = 1048583  # same 21-bit prime as the host row
    scheme = BasicShamirSharing(share_count=n, privacy_threshold=t,
                                prime_modulus=p)
    dim = 10_000
    plan = make_plan(scheme, dim)
    chunk = min(2_000, n_participants)
    rng = np.random.default_rng(13)
    key = jax.random.key(22)

    @jax.jit
    def step(secrets, key):
        # weight-grouped limb partials summed over the chunk's
        # participants; plain + across chunks is exact while
        # total_participants * L*K*127^2 < 2^63 (here ~1e10)
        return share_combine_limb(secrets, key, plan)

    t0 = time.perf_counter()
    acc = None
    plain = np.zeros(dim, dtype=np.int64)
    done = 0
    while done < n_participants and not _budget_spent(t0, done):
        c = min(chunk, n_participants - done)
        secrets = rng.integers(0, p, size=(c, dim))
        key, sub = jax.random.split(key)
        a = step(jnp.asarray(secrets), sub)
        acc = a if acc is None else acc + a
        plain += secrets.sum(axis=0)
        done += c
    clerk_sums = jnp.swapaxes(limb_recombine(acc, p), 0, 1)  # (n, B)
    survivors = [0, 2, 4]  # strict t+1=3 of 5: Lagrange on device
    got = positive(
        np.asarray(reconstruct(clerk_sums, survivors, scheme, dim)), p
    )
    wall = time.perf_counter() - t0
    out = {
        "config": f"3-device: basic-Shamir t=2 n=5 limb-MXU fabric, dim 10K, "
                  f"{n_participants} participants",
        "plane": "device-fabric (share arithmetic; transport priced by the host row)",
        "backend": jax.devices()[0].platform,
        "wall_s": round(wall, 3),
        "participants": done,
        "elements": done * dim,
        "elements_per_s": round(done * dim / wall, 1),
        "verified": bool(np.array_equal(got, plain % p)),
        "survivor_subset": survivors,
    }
    if done < n_participants:
        out["partial"] = True
    return out


def sumfirst_1m(n_participants: int) -> dict:
    """A real 1M-participant sum-first run (dim 1024, 61-bit) on host:
    the documented MAX_PARTICIPANTS=2^31 int64 bound exercised at the
    north star's participant count, bit-verified."""
    from sda_tpu.ops import find_packed_parameters
    from sda_tpu.ops.jaxcfg import ensure_x64
    from sda_tpu.ops.modular import positive
    from sda_tpu.parallel.engine import make_plan
    from sda_tpu.parallel.sumfirst import (
        clerk_sums_from_limb_acc,
        reconstruct_from_clerk_sums,
        value_limb_sums_chunk,
    )
    from sda_tpu.protocol import PackedShamirSharing

    ensure_x64()
    import jax
    import jax.numpy as jnp

    k, t, n = 5, 2, 8
    p, w2, w3 = find_packed_parameters(k, t, n, min_modulus_bits=60, seed=0)
    scheme = PackedShamirSharing(k, n, t, p, w2, w3)
    dim = 1024
    plan = make_plan(scheme, dim)
    chunk = 20_000
    rng = np.random.default_rng(5)
    key = jax.random.key(12)

    t0 = time.perf_counter()
    acc = None
    # independent verification: exact python-int column sums over a
    # sparse probe set (full object-dtype sums at 1M x 1024 would
    # dominate the wall clock without adding evidence)
    probe_cols = np.arange(0, dim, 97)
    probe = np.zeros(len(probe_cols), dtype=object)
    done = 0
    while done < n_participants:
        c = min(chunk, n_participants - done)
        secrets = rng.integers(p - (1 << 40), p, size=(c, dim)).astype(np.int64)
        key, sub = jax.random.split(key)
        a = np.asarray(value_limb_sums_chunk(jnp.asarray(secrets), sub, plan))
        acc = a if acc is None else acc + a
        probe += secrets[:, probe_cols].astype(object).sum(axis=0)
        done += c
    clerk_sums, _ = clerk_sums_from_limb_acc(acc, plan)
    out = reconstruct_from_clerk_sums(clerk_sums, range(n), scheme, dim)
    wall = time.perf_counter() - t0
    got = positive(np.asarray(out), p)
    want = np.array([int(v) % p for v in probe], dtype=np.int64)
    return {
        "config": f"sumfirst-1m: {n_participants} participants x {dim} dims, "
                  "61-bit, host",
        "wall_s": round(wall, 3),
        "participants": n_participants,
        "elements": n_participants * dim,
        "elements_per_s": round(n_participants * dim / wall, 1),
        "verified": bool(np.array_equal(got[probe_cols], want)),
        "verification": f"exact python-int sums on {len(probe_cols)} probe columns",
    }


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--out", default=None)
    parser.add_argument("--quick", action="store_true",
                        help="participant counts / 100 (smoke)")
    parser.add_argument("--configs", default=None,
                        help="comma-separated subset to run (default: all "
                        "host configs; with --device: 2,3,4)")
    parser.add_argument("--device", action="store_true",
                        help="route configs 2-4 through the TPU fabric "
                        "engines on the ambient JAX backend (VERDICT r4 "
                        "#4); config 4 is the same fabric code either "
                        "way, just not pinned to CPU")
    args = parser.parse_args()
    div = 100 if args.quick else 1
    if args.configs is None:
        args.configs = "2,3,4" if args.device else "1,2,3,4,sumfirst-1m"
    results = {"quick": args.quick, "device": args.device, "configs": []}
    arm_config_watchdog = None
    if args.device:
        # host-only rows must stay host rows: config 1 and sumfirst-1m
        # have no device analog (and no budget/partial support), and the
        # module header's promise that the HOST ladder never depends on
        # device health would silently break if they ran on the ambient
        # backend here.
        device_ok = {"2", "3", "4"}
        bad = [c for c in args.configs.split(",") if c.strip() not in device_ok]
        if bad:
            parser.error(
                f"--device supports configs 2,3,4 only (got {','.join(bad)}); "
                "run host-only configs without --device"
            )
        # cooperative per-config budget (between-chunk checks; see
        # _DEVICE_BUDGET) + a last-resort wedge watchdog re-armed before
        # every config: if a native device call blocks past
        # SDA_LADDER_DEADLINE the chip is wedged (a healthy-but-slow
        # config stops at its cooperative budget long before), so dump
        # the configs finished so far and exit — never leave the probe
        # loop hostage, never require an external SIGKILL. Per-config
        # arming keeps the deadline from accumulating across configs:
        # three slow-but-healthy configs must not eat config 4's slot.
        global _DEVICE_BUDGET
        _DEVICE_BUDGET = float(os.environ.get("SDA_LADDER_BUDGET", "300"))
        deadline = float(os.environ.get("SDA_LADDER_DEADLINE", "900"))

        def _wedged():
            results["watchdog"] = (
                f"deadline {deadline:.0f}s hit (device wedged mid-config?); "
                "partial results dumped"
            )
            payload = json.dumps(results, indent=1)
            print(payload)
            if args.out:
                Path(args.out).parent.mkdir(parents=True, exist_ok=True)
                Path(args.out).write_text(payload + "\n")
            os._exit(3)

        import threading

        wd_box = [None]

        def arm_config_watchdog():
            if wd_box[0] is not None:
                wd_box[0].cancel()
            wd_box[0] = threading.Timer(deadline, _wedged)
            wd_box[0].daemon = True
            wd_box[0].start()
    runners = {
        "1": lambda: config1(),
        "2": lambda: (config2_device if args.device else config2)(1_000 // div),
        "3": lambda: (config3_device if args.device else config3)(10_000 // div),
        "4": lambda: config4(100_000 // div),
        "sumfirst-1m": lambda: sumfirst_1m(1_000_000 // div),
    }
    for name in args.configs.split(","):
        name = name.strip()
        print(f"[ladder] running config {name}...", file=sys.stderr, flush=True)
        if arm_config_watchdog is not None:
            arm_config_watchdog()
        t0 = time.perf_counter()
        try:
            entry = runners[name]()
        except Exception as exc:  # record the failure, keep laddering
            entry = {"config": name, "error": f"{type(exc).__name__}: {exc}"}
        print(f"[ladder] config {name} done in "
              f"{time.perf_counter() - t0:.1f}s", file=sys.stderr, flush=True)
        results["configs"].append(entry)
    results["config5_north_star"] = (
        "the benchmark's c5-w61-d100k cells (benchmark/run.py, PERF.md); not re-run here"
    )
    payload = json.dumps(results, indent=1)
    print(payload)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(payload + "\n")
    ok = all(
        c.get("verified", True) and "error" not in c
        and c.get("output_matches_walkthrough", True)
        for c in results["configs"]
    )
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
