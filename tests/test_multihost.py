"""Hybrid ICI x DCN mesh fabric (parallel/multihost.py) on the virtual
8-device CPU mesh: the staged hierarchical reduction must produce exactly
the plaintext aggregate, and the DCN stage must carry only (n, B) partials
(structural: out spec replicated, psum staged by axis)."""

import numpy as np

from sda_tpu.ops import find_packed_parameters
from sda_tpu.ops.modular import positive
from sda_tpu.parallel.multihost import (
    hierarchical_secure_sum,
    make_hybrid_mesh,
    shard_participants_hybrid,
)
from sda_tpu.protocol import PackedShamirSharing


def _scheme():
    k, t, n = 3, 4, 8
    # the reference-verified p=433 vector (full_loop.rs:56-64)
    return PackedShamirSharing(
        secret_count=k, share_count=n, privacy_threshold=t,
        prime_modulus=433, omega_secrets=354, omega_shares=150,
    )


def test_hierarchical_sum_matches_plaintext():
    import jax
    import jax.numpy as jnp

    scheme = _scheme()
    mesh = make_hybrid_mesh(h_size=2, p_size=4)   # 2 "hosts" x 4 "chips"
    dim = scheme.secret_count * 4
    P_total = 2 * 4 * 3  # divisible by h*p

    rng = np.random.default_rng(0)
    secrets = rng.integers(0, scheme.prime_modulus, size=(P_total, dim))
    _, step = hierarchical_secure_sum(scheme, dim, mesh)
    out, plain = step(
        shard_participants_hybrid(jnp.asarray(secrets), mesh), jax.random.key(0)
    )
    got = positive(np.asarray(out), scheme.prime_modulus)
    want = positive(np.asarray(plain), scheme.prime_modulus)
    np.testing.assert_array_equal(got, want)
    # independent ground truth, off-device
    np.testing.assert_array_equal(
        want, secrets.sum(axis=0) % scheme.prime_modulus
    )


def test_hybrid_mesh_shapes():
    mesh = make_hybrid_mesh(h_size=2, p_size=4)
    assert mesh.shape == {"h": 2, "p": 4, "d": 1}
    mesh1 = make_hybrid_mesh(h_size=1, p_size=8)
    assert mesh1.shape == {"h": 1, "p": 8, "d": 1}
    mesh3 = make_hybrid_mesh(h_size=2, p_size=2, d_size=2)
    assert mesh3.shape == {"h": 2, "p": 2, "d": 2}


def test_hierarchical_sum_with_dim_axis():
    """Three-axis hybrid mesh (2 hosts x 2 chips x 2 dim shards): the
    dim/batch axis (sequence-parallel analog) stays sharded through the
    clerk sums; the aggregate must still equal the plaintext sum."""
    import jax
    import jax.numpy as jnp

    scheme = _scheme()
    mesh = make_hybrid_mesh(h_size=2, p_size=2, d_size=2)
    dim = scheme.secret_count * 2 * 3  # divisible by k * d_size
    secrets = np.random.default_rng(4).integers(
        0, scheme.prime_modulus, size=(8, dim)
    )
    _, step = hierarchical_secure_sum(scheme, dim, mesh)
    out, plain = step(
        shard_participants_hybrid(jnp.asarray(secrets), mesh), jax.random.key(2)
    )
    np.testing.assert_array_equal(
        positive(np.asarray(out), scheme.prime_modulus),
        secrets.sum(axis=0) % scheme.prime_modulus,
    )


def test_hierarchical_sum_generated_params():
    """Same over a generated 30-bit field (not the tiny test vector)."""
    import jax
    import jax.numpy as jnp

    k, t, n = 5, 2, 8
    p, w2, w3 = find_packed_parameters(k, t, n, min_modulus_bits=30, seed=0)
    scheme = PackedShamirSharing(
        secret_count=k, share_count=n, privacy_threshold=t,
        prime_modulus=p, omega_secrets=w2, omega_shares=w3,
    )
    mesh = make_hybrid_mesh(h_size=4, p_size=2)
    dim = k * 2
    secrets = np.random.default_rng(1).integers(0, p, size=(16, dim))
    _, step = hierarchical_secure_sum(scheme, dim, mesh)
    out, plain = step(
        shard_participants_hybrid(jnp.asarray(secrets), mesh), jax.random.key(1)
    )
    np.testing.assert_array_equal(
        positive(np.asarray(out), p), secrets.sum(axis=0) % p
    )


def test_fold_mesh_axes_distinct_per_device():
    """Every device must derive a distinct PRNG key (folding only one mesh
    axis would reuse share randomness across dim shards — a zero-privacy
    failure when shares differ only in the d coordinate)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from sda_tpu.parallel import make_mesh
    from sda_tpu.parallel.engine import fold_mesh_axes

    mesh = make_mesh(p_size=4, d_size=2)

    def per_device(key):
        return jax.random.key_data(fold_mesh_axes(key, mesh))[None]

    keys = jax.shard_map(
        per_device, mesh=mesh, in_specs=P(), out_specs=P(("p", "d")),
        check_vma=False,
    )(jax.random.key(0))
    rows = {tuple(np.asarray(k)) for k in keys}
    assert len(rows) == 8, "mesh devices derived colliding PRNG keys"


def test_hierarchical_wide_limb_accumulators():
    """Wide (61-bit) modulus on the hybrid mesh: per-device limb
    accumulators psum over ICI then DCN; one exact host recombine; the
    revealed aggregate equals the plaintext sum."""
    import jax
    import jax.numpy as jnp

    from sda_tpu.ops import find_packed_parameters
    from sda_tpu.parallel.engine import reconstruct
    from sda_tpu.parallel.limbmatmul import limb_recombine_host
    from sda_tpu.parallel.multihost import hierarchical_limb_accumulators
    from sda_tpu.protocol import PackedShamirSharing

    p, w2, w3 = find_packed_parameters(3, 4, 8, min_modulus_bits=60, seed=1)
    scheme = PackedShamirSharing(3, 8, 4, p, w2, w3)
    mesh = make_hybrid_mesh(h_size=2, p_size=2, d_size=2)
    dim = 3 * 2 * 3  # divisible by k * d_size
    secrets = (
        p - np.random.default_rng(6).integers(1, 5000, size=(8, dim))
    ).astype(np.int64)

    _, fn = hierarchical_limb_accumulators(scheme, dim, mesh)
    acc = np.asarray(
        fn(shard_participants_hybrid(jnp.asarray(secrets), mesh), jax.random.key(5))
    )
    clerk_sums = limb_recombine_host(acc, p).T
    out = reconstruct(jnp.asarray(clerk_sums), [1, 2, 3, 4, 5, 6, 7], scheme, dim)
    want = np.array(
        [sum(int(v) for v in secrets[:, j]) % p for j in range(dim)], dtype=np.int64
    )
    np.testing.assert_array_equal(positive(np.asarray(out), p), want)


def test_graft_entry_dryrun_all_fabrics():
    """The driver's multichip dry run must keep verifying every fabric
    (psum, all_to_all + dropout, hybrid h x p, wide limb) — run it as the
    driver does, on a virtual 8-device CPU mesh, and require each
    fabric's OK line."""
    import os
    import pathlib
    import subprocess
    import sys

    repo = pathlib.Path(__file__).resolve().parent.parent
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
    )
    out = subprocess.run(
        [sys.executable, str(repo / "__graft_entry__.py"), "8"],
        capture_output=True, text=True, env=env, cwd=repo, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-1000:]
    for marker in (
        "dryrun_multichip OK",
        "dryrun all_to_all fabric OK",
        "dropout reconstruction",
        "dryrun hybrid mesh OK",
        "dryrun wide (61-bit) sharded path OK",
    ):
        assert marker in out.stdout, (marker, out.stdout)


def test_two_process_distributed_round():
    """Drive initialize_distributed for real: two OS processes join one
    jax.distributed runtime (2 CPU devices each -> 4 global), build the
    hybrid mesh with ``h`` spanning processes, and verify the
    hierarchical secure sum end to end in both."""
    import os
    import pathlib
    import socket
    import subprocess
    import sys

    repo = pathlib.Path(__file__).resolve().parent.parent
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=2",
        PYTHONPATH=str(repo),
    )
    worker = str(repo / "tests" / "multihost_worker.py")
    procs = [
        subprocess.Popen(
            [sys.executable, worker, str(i), "2", str(port)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env, cwd=repo,
        )
        for i in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=300)
            outs.append((p.returncode, out, err))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    limitation = "Multiprocess computations aren't implemented on the CPU backend"
    if any(rc != 0 and limitation in err for rc, _, err in outs):
        import pytest

        pytest.skip(f"this jax build's CPU backend: {limitation}")
    for i, (rc, out, err) in enumerate(outs):
        assert rc == 0, f"proc {i} rc={rc}\n{err[-2000:]}"
        assert f"proc {i}/2 OK" in out, out
