"""Runnable demo: the TPU aggregation fabric itself — the engine that
turns the reference's per-clerk summation loop (client/src/clerk.rs:85-86,
combiner.rs:16-30) into device tensor programs.

Three stages, each verified against an independent plaintext sum:

1. single-device secure sum — per-participant packed-Shamir shares
   materialized on device (MXU int8-limb matmuls), clerk-combined,
   reconstructed;
2. sum-first streaming — share linearity (`share(Σv) = Σ share(v)`)
   reduces the hot loop to one exact limb-space integer reduction; the
   round driver (`sda_tpu.parallel.fold_round`) feeds host blocks to the
   device with a bounded number in flight and pairs the chunk entry with
   its epilogue; a clerk row is corrupted and DROPPED to show t+k-of-n
   reconstruction never reads it; then the same feed under the upstream's
   ChaCha masking (`fold_round(..., masking=)`): the clerks sum masked rows,
   the feed hands on every row's seed, the recipient unmasks from the seeds;
3. the sharded fabric — the same sum-first loop over a device Mesh
   (participants sharded over axis ``p``, dims over ``d``), one int64
   ``psum`` carrying the tiny accumulator across the mesh; then the masked
   round over a mesh through the round driver (`fold_round(..., masking=,
   mesh=)`): every chip masks its own rows, the recipient's re-expansion is
   sharded too.

Run:  python examples/secure_sum_fabric.py
(forces an 8-device virtual CPU mesh so it runs anywhere — an ambient
JAX_PLATFORMS is deliberately overridden, because inheriting a remote
TPU platform would block the demo on device health; set
SDA_EXAMPLE_REAL_DEVICES=1 on actual TPU hardware to run the same code
over the real chips)
"""

import os
import sys

# 8 virtual devices BEFORE jax imports (append — don't clobber ambient
# XLA_FLAGS like --xla_dump_to)
if not os.environ.get("SDA_EXAMPLE_REAL_DEVICES"):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from sda_tpu.ops import find_packed_parameters
from sda_tpu.ops.jaxcfg import ensure_x64

ensure_x64()

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sda_tpu.ops.modular import positive
from sda_tpu.parallel import TpuAggregator, fold_round
from sda_tpu.parallel.sumfirst import (
    clerk_sums_from_limb_acc,
    reconstruct_from_clerk_sums,
    sharded_value_limb_sums,
    value_limb_sums_chunk,
)
from sda_tpu.protocol import ChaChaMasking, PackedShamirSharing


def main():
    # packed Shamir: k=5 secrets per batch, privacy threshold t=2,
    # n=8 clerks, 30-bit prime with the radix-2/radix-3 root structure
    # the share/reconstruct NTT domains need (crypto.rs:146-153)
    k, t, n = 5, 2, 8
    p, w2, w3 = find_packed_parameters(k, t, n, min_modulus_bits=30, seed=0)
    scheme = PackedShamirSharing(k, n, t, p, w2, w3)
    dim = 2_000
    rng = np.random.default_rng(0)

    # --- 1. single-device secure sum ------------------------------------
    participants = 256
    secrets = rng.integers(0, p, size=(participants, dim))
    agg = TpuAggregator(scheme, dim, use_limbs=True)
    out = agg.secure_sum(jnp.asarray(secrets), jax.random.key(1))
    got = positive(np.asarray(out), p)
    want = secrets.sum(axis=0) % p
    assert np.array_equal(got, want)
    print(f"1. single-device secure sum OK: {participants} x {dim}, p={p}")

    # --- 2. sum-first streaming + clerk dropout -------------------------
    driver = fold_round(scheme, dim, value_limb_sums_chunk, 512)
    plain = np.zeros(dim, dtype=np.int64)

    def streamed():  # four host blocks, each made as the feed asks for it
        for _ in range(0, 2_048, 512):
            block = rng.integers(0, p, size=(512, dim))
            np.add(plain, block.sum(axis=0), out=plain)
            yield block.astype(driver.input_dtype)

    acc = driver.fold_host_rows(streamed(), jax.random.key(2), in_flight=2)
    clerk_sums = driver.clerk_sums(acc)
    clerk_sums[3] = -7  # corrupt the dropped clerk: must never be read
    survivors = [i for i in range(n) if i != 3][: scheme.reconstruction_threshold]
    assert np.array_equal(driver.reveal(clerk_sums, survivors), plain % p)
    print(f"2. sum-first stream OK: 2048 participants, clerk 3 dropped, "
          f"reconstructed from {len(survivors)} of {n} clerk sums")

    # the same feed, masked: every row under a fresh 128-bit seed
    masked_round = fold_round(
        scheme, dim, value_limb_sums_chunk, 512, masking=ChaChaMasking(p, dim, 128)
    )
    blocks = [rng.integers(0, p, size=(512, dim)).astype(driver.input_dtype) for _ in range(2)]
    acc, seeds, counts = masked_round.fold_host_rows(blocks, jax.random.key(4), in_flight=2)
    assert masked_round.short_windows(counts) == 0  # no mask ran out of keystream
    masked = masked_round.reveal(masked_round.clerk_sums(acc), survivors)
    want = np.concatenate(blocks).astype(np.int64).sum(axis=0) % p
    assert not np.array_equal(masked, want)  # the clerks saw masked sums
    uploads = list(np.concatenate([np.asarray(s) for s in seeds]).astype(np.int64))
    assert np.array_equal(masked_round.unmask(masked, uploads, chunk=512), want)
    print("2b. masked stream OK: 1024 participants, a seed each handed on by the feed, "
          "unmasked from the seeds alone")

    # --- 3. the sharded fabric over a device mesh -----------------------
    # fit the mesh to whatever devices exist (8 virtual CPUs by default;
    # real chips under SDA_EXAMPLE_REAL_DEVICES — 4x2 on 8, 2x2 on 4, ...)
    devs = jax.devices()
    d_size = 2 if len(devs) >= 2 else 1  # dim axis: k*d must divide dim
    p_size = min(4, len(devs) // d_size)
    devices = np.array(devs[: p_size * d_size]).reshape(p_size, d_size)
    mesh = Mesh(devices, axis_names=("p", "d"))
    plan = driver.plan
    fabric = sharded_value_limb_sums(plan, mesh)
    shard = rng.integers(0, p, size=(1_024, dim))
    sharded = jax.device_put(
        jnp.asarray(shard), NamedSharding(mesh, P("p", "d"))
    )
    acc = np.asarray(fabric(sharded, jax.random.key(3)))
    clerk_sums, _ = clerk_sums_from_limb_acc(acc, plan)
    out = reconstruct_from_clerk_sums(clerk_sums, range(n), scheme, dim)
    assert np.array_equal(positive(np.asarray(out), p), shard.sum(axis=0) % p)
    print(f"3. sharded fabric OK: mesh p={mesh.shape['p']} x d={mesh.shape['d']}, "
          "limb accumulator psum'd across the mesh, aggregate verified")

    # the masked round over a mesh: the layout is an argument of the round
    # as the masking scheme is; every chip masks its own rows under seeds of
    # its own, and the recipient's re-expansion runs where the seeds lie
    flat = Mesh(np.array(devs[: p_size * d_size]).reshape(-1, 1), axis_names=("p", "d"))
    mesh_round = fold_round(
        scheme, dim, value_limb_sums_chunk, 1_024, masking=ChaChaMasking(p, dim, 128), mesh=flat
    )
    rows = jax.device_put(jnp.asarray(shard), NamedSharding(flat, P("p", "d")))
    acc, seeds, counts = mesh_round.fold_chunks([rows], jax.random.key(5))
    assert mesh_round.short_windows(counts) == 0
    masked = mesh_round.reveal(mesh_round.clerk_sums(acc), survivors)
    assert not np.array_equal(masked, shard.sum(axis=0) % p)
    assert np.array_equal(mesh_round.unmask(masked, seeds), shard.sum(axis=0) % p)
    print(f"3b. masked round over the mesh OK: p={flat.shape['p']}, every chip's own seeds, "
          "unmasked from the seeds where they lie")


if __name__ == "__main__":
    main()
