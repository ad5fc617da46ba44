"""Multi-host distribution: hybrid ICI x DCN meshes and hierarchical sums.

The reference's "distributed backend" is HTTP pull-queues between
independent phone processes (SURVEY.md §5 — no NCCL/MPI anywhere); it
scales hosts by adding more clerks. The TPU fabric's equivalent for
multi-host *pods* is jax.distributed + a hybrid mesh: a fast ICI axis
inside each slice and a slow DCN axis across hosts, with the reduction
staged so that only the tiny per-clerk partial sums ever cross DCN.

Topology mapping:

- axis ``h`` (hosts / slices, DCN): coarse participant sharding — each
  host ingests its own participant population, like each region of
  phones talking to its nearest collector.
- axis ``p`` (chips within a slice, ICI): fine participant sharding.
- The per-device work is the usual share+combine; the cross-device sum
  runs ``psum`` over ``p`` first (ICI — cheap, wide), then over ``h``
  (DCN — only ``(n, B)`` int64 partials, KBs, regardless of how many
  participants each host holds). Like the sum-first engine
  (parallel/sumfirst.py), linearity is what keeps the big tensors local.

Everything here is expressed in mesh axes, not transport: on one
process with 8 CPU devices the same code runs with ``h`` and ``p`` both
mapped to local devices (how tests and the driver dry-run validate it);
on a real multi-host pod the identical program runs under
``jax.distributed`` with ``h`` spanning slices.
"""

from __future__ import annotations


def initialize_distributed(coordinator_address=None, num_processes=None, process_id=None):
    """Join the multi-process JAX runtime (call once per host, before any
    jax op). Thin, explicit wrapper over ``jax.distributed.initialize`` —
    on TPU pods all three arguments are auto-detected from the metadata
    server and may be omitted."""
    import jax

    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def make_hybrid_mesh(
    h_size: int | None = None, p_size: int | None = None, d_size: int = 1
):
    """Mesh with axes ``("h", "p", "d")``: hosts (DCN) x chips-per-host
    (ICI, participant axis) x dim batches (ICI, the dimension-batching /
    sequence-parallel axis for 100K-dim vectors).

    Under ``jax.distributed`` with multiple processes, uses
    ``mesh_utils.create_hybrid_device_mesh`` so ``h`` is laid out across
    slices and ``p``/``d`` within them (those collectives ride ICI).
    There ``h_size`` is *derived* from the topology (the slice count on
    multi-slice pods, else the process count); passing it explicitly is
    only a cross-check — a value that miscounts the granule raises.
    Single-process (tests, dry runs): plain reshape of local devices —
    same program, simulated topology — and ``h_size`` is free.
    """
    import jax
    import numpy as np

    devices = jax.devices()
    n_proc = jax.process_count()
    if n_proc > 1:
        from jax.experimental import mesh_utils
        from jax.sharding import Mesh

        # multi-slice TPU pods: the DCN unit is the slice. Anywhere
        # slice_index doesn't distinguish devices (multi-process CPU
        # reports slice 0 everywhere; single-slice multi-host pods too),
        # the process is the outer-network unit — and the h default must
        # count the same granules the mesh builder will group by.
        slice_ids = {getattr(d, "slice_index", None) for d in devices}
        by_process = (None in slice_ids) or len(slice_ids) == 1
        granules = n_proc if by_process else len(slice_ids)
        if h_size is not None and h_size != granules:
            # the mesh builder groups devices by granule (process or
            # slice); an h_size counting the wrong unit — e.g. processes
            # on a multi-slice pod where the DCN unit is the slice —
            # would otherwise surface as an opaque reshape error deep in
            # create_hybrid_device_mesh
            unit = "process" if by_process else "slice"
            raise ValueError(
                f"h_size {h_size} != {granules} DCN granules: the outer "
                f"mesh axis is laid out per {unit} on this topology, so "
                f"h_size must equal the {unit} count ({granules}); omit "
                "h_size to use it"
            )
        h_size = granules
        p_size = p_size or (len(devices) // (h_size * d_size))
        grid = mesh_utils.create_hybrid_device_mesh(
            mesh_shape=(1, p_size, d_size),
            dcn_mesh_shape=(h_size, 1, 1),
            devices=devices,
            process_is_granule=by_process,
        )
        return Mesh(grid, ("h", "p", "d"))
    from jax.sharding import Mesh

    if h_size is None:
        h_size = 2 if len(devices) % 2 == 0 and len(devices) > 1 else 1
    p_size = p_size or (len(devices) // (h_size * d_size))
    need = h_size * p_size * d_size
    if need > len(devices):
        raise ValueError(f"need {need} devices, have {len(devices)}")
    grid = np.array(devices[:need]).reshape(h_size, p_size, d_size)
    return Mesh(grid, ("h", "p", "d"))


def shard_participants_hybrid(array, mesh):
    """(P, dim) sharded: participants over host+chip axes, dim over d."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.device_put(array, NamedSharding(mesh, P(("h", "p"), "d")))


def hierarchical_clerk_sums(scheme, dim: int, mesh):
    """Jitted share+combine over a hybrid mesh with a staged reduction.

    Returns ``fn(secrets_sharded, key) -> (n, B)`` clerk sums (replicated).
    Stage 1 shares + locally combines each device's participant slice;
    stage 2 psums over ``p`` (ICI); stage 3 psums the already-reduced
    ``(n, B)`` partials over ``h`` (DCN) — the only cross-host traffic.
    Bit-identical to the single-mesh engine for the same key-folding
    layout (tested on a virtual hybrid mesh).
    """
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from .engine import (
        TpuAggregator,
        _check_psum_bound,
        clerk_combine_mod,
        share_participants,
    )

    agg = TpuAggregator(scheme, dim, mesh=mesh)
    plan = agg.plan
    agg.validate_d_sharding(dim)
    _check_psum_bound(mesh.shape["p"], plan.modulus, "hierarchical_clerk_sums(p)")
    _check_psum_bound(mesh.shape["h"], plan.modulus, "hierarchical_clerk_sums(h)")
    import jax.numpy as jnp

    from .engine import fold_mesh_axes

    def local_step(secrets, key):
        key = fold_mesh_axes(key, mesh)
        shares = share_participants(secrets, key, plan, False)
        partial = clerk_combine_mod(shares, plan.modulus)
        partial = lax.rem(lax.psum(partial, axis_name="p"), jnp.int64(plan.modulus))
        # DCN stage: (n, B_local) int64 per host — KBs, independent of P
        total = lax.psum(partial, axis_name="h")
        return lax.rem(total, jnp.int64(plan.modulus))

    d_spec = "d" if "d" in mesh.axis_names else None
    mapped = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(P(("h", "p"), d_spec), P()),
        out_specs=P(None, d_spec),  # clerk sums replicated; B stays d-sharded
        check_vma=False,
    )
    return agg, jax.jit(mapped)


def hierarchical_limb_accumulators(scheme, dim: int, mesh):
    """Wide-modulus (61-bit) twin of :func:`hierarchical_clerk_sums`.

    Per-device fused limb share+combine (no mod ops on device — see
    ``engine.sharded_limb_accumulators``), int64 partial psum over ``p``
    (ICI), then over ``h`` — the only DCN traffic is the tiny
    ``(W, B_local, n)`` accumulator. Epilogue: one exact host
    ``limb_recombine_host(acc, p).T`` then ``reconstruct``. int64 stays
    exact to ~5e12 total participants.

    Returns ``(agg, fn)`` with ``fn(secrets_sharded, key) -> (W, B, n)``
    int64 accumulators (replicated; B d-sharded).
    """
    import jax
    from jax.sharding import PartitionSpec as P

    from .engine import TpuAggregator

    agg = TpuAggregator(scheme, dim, mesh=mesh)
    agg.validate_d_sharding(dim)

    d_spec = "d" if "d" in mesh.axis_names else None
    mapped = jax.shard_map(
        # ICI ("p") before DCN ("h"): only the tiny accumulator crosses hosts
        agg._limb_accumulator_local_step(("p", "h")),
        mesh=mesh,
        in_specs=(P(("h", "p"), d_spec), P()),
        out_specs=P(None, d_spec, None),
        check_vma=False,
    )
    return agg, jax.jit(mapped)


def hierarchical_secure_sum(scheme, dim: int, mesh):
    """Full multi-host round: sharded share/combine + reconstruct + an
    independent plaintext-sum verification path (same contract as
    ``engine.full_training_step``, over the hybrid mesh)."""
    from .engine import verified_step

    agg, sums_fn = hierarchical_clerk_sums(scheme, dim, mesh)
    return agg, verified_step(agg, sums_fn)
