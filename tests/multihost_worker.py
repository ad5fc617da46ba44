"""Worker for the two-process jax.distributed smoke test.

Each process runs this file with (process_id, num_processes, coordinator
port); both bring 2 local CPU devices, so the joined runtime has a 4-device
global mesh with the ``h`` axis genuinely spanning processes — the real
``jax.distributed`` path that single-process virtual meshes cannot reach.
Run via tests/test_multihost.py::test_two_process_distributed_round.
"""

import os
import sys


def main() -> int:
    proc_id, nprocs, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=2")

    from sda_tpu.ops.jaxcfg import ensure_x64
    from sda_tpu.parallel.multihost import initialize_distributed

    initialize_distributed(
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=nprocs,
        process_id=proc_id,
    )

    import jax

    assert jax.process_count() == nprocs, jax.process_count()
    assert len(jax.devices()) == 2 * nprocs, jax.devices()
    ensure_x64()
    import jax.numpy as jnp
    import numpy as np

    from sda_tpu.ops.modular import positive
    from sda_tpu.parallel.multihost import (
        hierarchical_secure_sum,
        make_hybrid_mesh,
        shard_participants_hybrid,
    )
    from sda_tpu.protocol import PackedShamirSharing

    scheme = PackedShamirSharing(3, 8, 4, 433, 354, 150)
    dim = 24
    mesh = make_hybrid_mesh()  # h = process count, p = chips per process
    assert mesh.shape["h"] == nprocs, mesh.shape

    # an explicit h_size that miscounts the DCN granule must be a targeted
    # error naming the granule unit, not a reshape failure inside the mesh
    # builder (multi-process branch only — single-process reshapes freely)
    try:
        make_hybrid_mesh(h_size=nprocs * 2)
    except ValueError as e:
        assert "DCN granules" in str(e), e
    else:
        raise AssertionError("wrong explicit h_size did not raise")

    # every process holds the same global array (same seed); device_put
    # splits it across the global mesh, each process keeping its shards
    rng = np.random.default_rng(7)
    secrets = rng.integers(0, scheme.prime_modulus, size=(8, dim))
    agg, step = hierarchical_secure_sum(scheme, dim, mesh)
    out, plain = step(
        shard_participants_hybrid(jnp.asarray(secrets), mesh), jax.random.key(0)
    )
    got = positive(np.asarray(out), scheme.prime_modulus)
    want = positive(np.asarray(plain), scheme.prime_modulus)
    assert np.array_equal(got, want), "distributed aggregate != plaintext sum"
    assert np.array_equal(want, secrets.sum(axis=0) % scheme.prime_modulus)
    print(
        f"proc {proc_id}/{nprocs} OK: h={mesh.shape['h']} p={mesh.shape['p']} "
        f"distributed aggregate verified",
        flush=True,
    )
    jax.distributed.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
