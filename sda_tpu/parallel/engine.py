"""The TPU aggregation fabric — SDA's hot loop as sharded mod-p kernels.

This is the ``device="tpu"`` execution path of the north star
(/root/repo/BASELINE.json): the share-generate / clerk-combine / reconstruct
pipeline over an HBM-resident ``(participants, dim)`` tensor, replacing the
reference's per-phone Rust loops (client/src/crypto/sharing/*,
client/src/clerk.rs:85-86) when participants are simulated or co-hosted on
an accelerator slice.

Pipeline (all mod p, truncated-remainder representatives):

1. *share*: reshape ``(P, dim) -> (P, B, k)`` batches (zero-padding the dim
   tail exactly like batched.rs:30-43), append ``(P, B, t)`` counter-based
   randomness, one batched matmul with the precomputed share matrix
   ``(k+t, n)`` -> ``(P, B, n)``. The NTT pipeline is folded into that
   matrix on host (ops/shamir.py) — on the MXU a matmul IS the fast NTT at
   these domain sizes.
2. *transpose + clerk-combine*: the server-side (participants x clerks)
   transpose (server/src/snapshot.rs, stores.rs:86-101) is an axis
   permutation here; the per-clerk modular sum is a single reduction over
   the participant axis. Sharded over a mesh ``p`` axis this is a local
   partial sum + ``psum`` riding ICI — no per-participant traffic at all.
3. *reconstruct*: gather any ``reconstruction_threshold`` surviving clerk
   rows, one ``(R, k)`` Lagrange matmul, truncate the pad
   (batched.rs:68-98).

Sharding model: ``Mesh(axes p, d)`` — participants shard over ``p``
(the reference's "many phones" axis), the dim/batch axis shards over ``d``
(the reference's dimension-batching axis, SURVEY.md §2.3). Clerk results
are tiny (n x B); they end replicated after the psum, which is exactly what
the recipient needs.

dtype discipline: values live in int32 (p < 2^31), arithmetic widens to
int64 only where products/sums require it. The int8-limb MXU path
(``limbmatmul``) replaces the widening matmul on TPU in ``share_combine_limb``,
the chunk step of the benchmark's per-participant cell
(``benchmark/rounds/packed_fold.py``).
"""

from __future__ import annotations

import time
from dataclasses import dataclass

import numpy as np

from .. import telemetry
from ..ops import shamir
from ..ops.jaxcfg import ensure_x64
from ..protocol import AdditiveSharing, BasicShamirSharing, PackedShamirSharing


def _step_hist(step: str):
    return telemetry.histogram(
        "sda_engine_step_seconds",
        "secure_sum stage timing (host dispatch unless JAX blocks)",
        step=step,
    )


@dataclass(frozen=True)
class AggregationPlan:
    """Host-precomputed constants for a scheme + dimension."""

    modulus: int
    dim: int
    input_size: int  # k (1 for additive)
    rand_size: int  # t for packed, n-1 for additive
    share_count: int  # n
    n_batches: int  # B = ceil(dim / k)
    share_matrix: np.ndarray | None  # (n, k+t) packed; None for additive


def make_plan(scheme, dim: int) -> AggregationPlan:
    if isinstance(scheme, (BasicShamirSharing, PackedShamirSharing)):
        k = scheme.input_size  # secret_count for packed, 1 for basic
        return AggregationPlan(
            modulus=scheme.prime_modulus,
            dim=dim,
            input_size=k,
            rand_size=scheme.privacy_threshold,
            share_count=scheme.share_count,
            n_batches=-(-dim // k),
            share_matrix=shamir.share_matrix(scheme),
        )
    if isinstance(scheme, AdditiveSharing):
        return AggregationPlan(
            modulus=scheme.modulus,
            dim=dim,
            input_size=1,
            rand_size=scheme.share_count - 1,
            share_count=scheme.share_count,
            n_batches=dim,
            share_matrix=None,
        )
    raise TypeError(f"unknown sharing scheme {scheme!r}")


# ---------------------------------------------------------------------------
# Device kernels (pure, jittable). All take/return jnp arrays.
# ---------------------------------------------------------------------------


def _jnp():
    ensure_x64()
    import jax.numpy as jnp

    return jnp


def _batch_secrets(secrets, plan: AggregationPlan):
    """(P, d) -> (P, b, k) with zero-padded tail (batched.rs semantics).

    Shape-driven (not plan.dim-driven): inside ``shard_map`` the dim axis is
    a local shard, so the batch count comes from the actual input. The
    sharded path requires dim divisible by k * d_size, so padding only ever
    happens at the true global tail.
    """
    jnp = _jnp()
    import jax

    P, d = secrets.shape
    nb = -(-d // plan.input_size)
    pad = nb * plan.input_size - d
    with jax.named_scope("fabric.input/batch"):
        padded = jnp.pad(secrets, ((0, 0), (0, pad)))
        return padded.reshape(P, nb, plan.input_size)


def _device_randomness(key, shape, modulus):
    """Counter-based uniform draws in [0, modulus) (simulation-grade RNG —
    real participants draw on their own hosts; see ops/rng.py)."""
    import jax

    from ..ops.rng import uniform_mod_device

    with jax.named_scope("fabric.rand/draw"):
        return uniform_mod_device(key, shape, modulus)


def share_participants(secrets, key, plan: AggregationPlan, use_limbs: bool = False):
    """(P, dim) secrets -> (P, n, B) per-clerk share tensor."""
    jnp = _jnp()
    from jax import lax

    p = plan.modulus
    if plan.share_matrix is None:
        # additive: n-1 uniform draws + closing share (additive.rs:42-48)
        P, d = secrets.shape
        draws = _device_randomness(key, (P, plan.share_count - 1, d), p)  # (P, n-1, d)
        # a plain int64 sum of the n-1 draws overflows once
        # (n-1)*(p-1) >= 2^63, silently corrupting the closing share;
        # the auto dispatch switches to the halving mod-sum there
        from ..ops.modular import mod_sum_auto_jnp

        total = mod_sum_auto_jnp(draws, p, axis=1)
        last = lax.rem(secrets.astype(jnp.int64) - total, jnp.int64(p))
        return jnp.concatenate([draws.astype(jnp.int64), last[:, None, :]], axis=1)

    batches = _batch_secrets(secrets, plan)  # (P, b, k)
    P, nb = batches.shape[0], batches.shape[1]
    randomness = _device_randomness(key, (P, nb, plan.rand_size), p)
    if use_limbs:
        from .limbmatmul import limb_modmatmul_const

        # keep the big tensor in native int32 lanes when the field fits
        dt = jnp.int32 if p <= (1 << 31) else jnp.int64
        values = jnp.concatenate(
            [batches.astype(dt), randomness.astype(dt)], axis=-1
        )
        flat = values.reshape(-1, values.shape[-1])
        shares = limb_modmatmul_const(flat, plan.share_matrix.T, p).reshape(P, nb, -1)
    else:
        values = jnp.concatenate(
            [batches.astype(jnp.int64), randomness.astype(jnp.int64)], axis=-1
        )
        S_T = jnp.asarray(plan.share_matrix.T)  # (k+t, n)
        if p >= (1 << 31):
            raise ValueError(
                "int64 share products overflow for p >= 2^31; use the limb "
                "path (share_combine_limb + limb_recombine_host)"
            )
        prods = lax.rem(values[..., :, None] * S_T[None, None, :, :], jnp.int64(p))
        shares = lax.rem(jnp.sum(prods, axis=-2), jnp.int64(p))  # (P, B, n)
    return jnp.swapaxes(shares, 1, 2)  # (P, n, B)


def count_share_combine(path: str) -> None:
    """One per-participant share + combine traced, by the path it takes. The
    path is a property of the traced program, as a limb sum's road is
    (``sumfirst.count_limb_sum_road``): counted where it is chosen, once a
    trace."""
    telemetry.counter(
        "sda_fabric_share_combine_total",
        "per-participant share + combine programs traced, by path "
        "(fused | interpret | xla)",
        path=path,
    ).inc()


def _values_by_dim(secrets, randomness, plan: AggregationPlan):
    """(C, d) secrets and their (C, b, t) draw as the kernel takes them
    (``limb_pallas.participant_limb_sums_pallas``), participants on the
    lanes: the chunk transposed once, (b·k, C) int32, its dim tail
    zero-padded as ``_batch_secrets`` pads it (value ``s`` of batch ``j`` is
    row ``j·k + s``: the de-interleave is left to the kernel's loads), and
    the draw as (t, b, C): a layout to the compiler, not a copy. Narrow
    fields only (canonical values fit int32)."""
    jnp = _jnp()
    import jax
    from jax import lax

    k = plan.input_size
    d = secrets.shape[1]
    with jax.named_scope("fabric.values"):
        padded = jnp.pad(secrets.astype(jnp.int32), ((0, 0), (0, -d % k)))
        # the transpose of a step's parameter is a layout change to the
        # compiler, a copy that carries the parameter's name and no scope:
        # behind a barrier (no operation: it is gone before code is made) the
        # copy is this scope's, and the cell's layout seconds have a name
        by_dim = lax.optimization_barrier(padded).T
    with jax.named_scope("fabric.rand/layout"):
        return by_dim, jnp.transpose(randomness.astype(jnp.int32), (2, 1, 0))


def _combine_fused(secrets, randomness, *, plan: AggregationPlan, interpret=False):
    """The fold on the kernel ``limb_share_combine``: the transposed chunk
    and the draw in, the (W, b, n) accumulator out; no per-participant
    tensor in between."""
    jnp = _jnp()
    import jax

    from .limb_pallas import participant_limb_sums_pallas
    from .limbmatmul import fold_const_limbs

    stacks = fold_const_limbs(plan.share_matrix.T, plan.modulus)  # (L, L*(k+t), n)
    by_dim, draws = _values_by_dim(secrets, randomness, plan)
    with jax.named_scope("fabric.share_matmul/dot"):
        acc = participant_limb_sums_pallas(by_dim, draws, stacks, interpret=interpret)
    with jax.named_scope("fabric.combine"):
        return acc.astype(jnp.int64)  # (W=L, b, n)


def _combine_xla(secrets, randomness, *, plan: AggregationPlan):
    """The fold as XLA's own operations: (C·b, k+t) value rows, L int8 dots,
    the per-participant partials summed over the participant axis."""
    jnp = _jnp()
    import jax

    from .limbmatmul import fold_const_limbs, limb_partials_const

    p = plan.modulus
    stacks = fold_const_limbs(plan.share_matrix.T, p)  # (L, L*(k+t), n)
    batches = _batch_secrets(secrets, plan)  # (C, b, k)
    C, nb = batches.shape[0], batches.shape[1]
    with jax.named_scope("fabric.values"):
        # keep the big tensor in native int32 lanes when the field fits
        dt = jnp.int32 if p <= (1 << 31) else jnp.int64
        values = jnp.concatenate([batches.astype(dt), randomness.astype(dt)], axis=-1)
        values = values.reshape(C * nb, -1)
    partials = limb_partials_const(values, stacks, p)  # (W=L, C*nb, n)
    W, LK = stacks.shape[0], stacks.shape[1]
    with jax.named_scope("fabric.combine"):
        per_part = partials.reshape(W, C, nb, -1)
        # participant-axis reduction: stay in int32 when the bound allows
        # (partial elements <= L*K * 127^2), halving the reduction cost
        if C * LK * 127 * 127 < 2**31:
            return jnp.sum(per_part, axis=1).astype(jnp.int64)  # (W, b, n)
        return jnp.sum(per_part.astype(jnp.int64), axis=1)  # (W, b, n)


def _share_draw(secrets, key, plan: AggregationPlan):
    """A chunk's share randomness, (C, b, t): the one draw of a step, the
    same for every formulation of the fold."""
    C, d = secrets.shape
    nb = -(-d // plan.input_size)
    return _device_randomness(key, (C, nb, plan.rand_size), plan.modulus)


def share_combine_limb_xla(secrets, key, plan: AggregationPlan):
    """:func:`share_combine_limb` in XLA's formulation at every width and
    shape: what runs off the TPU, over a wide field and past the kernel's
    int32 bound, and the plain reference the fused kernel is held to, bit for
    bit for one key (tests/test_parallel_engine.py,
    ``chip_smoke.kernel_parity``). No engine calls it by name."""
    return _combine_xla(secrets, _share_draw(secrets, key, plan), plan=plan)


def share_combine_limb(secrets, key, plan: AggregationPlan):
    """Fused share + clerk-combine in limb space: (C, d) -> (W, b, n) int64.

    The hot loop stays division-free: int8 MXU matmuls produce weight-grouped
    partials, which are *summed over the participant axis first* (linearity)
    and only then carried as a tiny (W, b, n) accumulator. Callers reduce
    accumulators across chunks with ``lax.rem`` (values stay < p) and call
    ``limb_recombine`` once at the very end: emulated 64-bit multiply/divide
    never touches the (participants x dim) tensor.

    One algorithm, two layouts, chosen from the platform the program is
    lowered for (``lax.platform_dependent``), the field's width and the
    chunk's shape (``limb_pallas.fused_fits``): on a TPU a narrow field's
    chunk goes transposed, participants on the lanes, through the kernel
    ``limb_share_combine`` (``limb_pallas``); everything else takes
    :func:`share_combine_limb_xla`'s operations. Same draw, same accumulator,
    bit for bit. ``sda_fabric_share_combine_total{path}`` counts the choice
    as this process's backend makes it, which is what runs it. Inside a
    ``shard_map`` a narrow call would take the kernel on its local block; no
    caller in the tree makes one (the sharded limb fabric is the wide field's).
    """
    import functools

    import jax
    from jax import lax

    from .limb_pallas import fused_fits

    randomness = _share_draw(secrets, key, plan)
    xla = functools.partial(_combine_xla, plan=plan)
    if not fused_fits(plan.modulus, secrets.shape[0], plan.input_size + plan.rand_size):
        count_share_combine("xla")
        return xla(secrets, randomness)
    count_share_combine("fused" if jax.default_backend() == "tpu" else "xla")
    return lax.platform_dependent(
        secrets, randomness, tpu=functools.partial(_combine_fused, plan=plan), default=xla
    )


def clerk_combine(shares):
    """(P, n, B) -> (n, B) local modular sums — the clerk hot loop
    (combiner.rs:16-30) as one reduction; caller supplies the modulus rem.

    Exact only while P*(p-1) < 2^63. No engine calls this: every path goes
    through :func:`clerk_combine_mod`. It stays as the plain reference the
    tests hold the sum-first path to (tests/test_sumfirst.py)."""
    jnp = _jnp()
    return jnp.sum(shares.astype(jnp.int64), axis=0)


def clerk_combine_mod(shares, p: int):
    """Reduced clerk sums over the participant axis, exact for any p < 2^62.

    In the narrow regime (P*(p-1) < 2^63) this is bit-identical to
    ``lax.rem(clerk_combine(shares), p)``; past the bound a plain int64 sum
    silently wraps, so the halving mod-sum takes over — required for
    additive sharing at 61-bit moduli (additive.rs:55-73 semantics)."""
    _jnp()
    from ..ops.modular import mod_sum_auto_jnp

    return mod_sum_auto_jnp(shares, p, axis=0)


def reconstruct(clerk_sums, indices, scheme, dim: int):
    """(n, B) clerk sums + surviving ``indices`` -> (dim,) aggregate."""
    jnp = _jnp()
    from jax import lax

    if isinstance(scheme, AdditiveSharing):
        # wide moduli: n reduced rows still overflow a plain int64 sum
        from ..ops.modular import mod_sum_auto_jnp

        return mod_sum_auto_jnp(clerk_sums.astype(jnp.int64), scheme.modulus, axis=0)[
            :dim
        ]
    p = scheme.prime_modulus
    if p >= (1 << 31):
        # wide modulus: tiny matrices, exact host interpolation
        return jnp.asarray(
            shamir.reconstruct_clerk_sums_host(clerk_sums, indices, scheme, dim)
        )
    L = jnp.asarray(shamir.reconstruction_matrix(scheme, list(indices)))  # (k, R)
    rows = clerk_sums[jnp.asarray(list(indices))]  # (R, B)
    prods = lax.rem(L[:, :, None] * rows[None, :, :], jnp.int64(p))
    secrets = lax.rem(jnp.sum(prods, axis=1), jnp.int64(p))  # (k, B)
    return secrets.T.reshape(-1)[:dim]


# ---------------------------------------------------------------------------
# The sharded engine
# ---------------------------------------------------------------------------


class TpuAggregator:
    """End-to-end secure-sum engine over a device mesh.

    ``mesh`` axes: ``"p"`` shards participants, ``"d"`` shards the
    batch/dim axis. Single-device use passes ``mesh=None``.
    """

    def __init__(self, scheme, dim: int, mesh=None, use_limbs: bool = False):
        self.scheme = scheme
        self.dim = dim
        self.plan = make_plan(scheme, dim)
        self.mesh = mesh
        self.use_limbs = use_limbs

    # -- single-device reference path --------------------------------------

    def secure_sum(self, secrets, key, indices=None):
        """(P, dim) -> (dim,) aggregate, all on device."""
        p = self.plan.modulus
        with telemetry.span("engine.secure_sum", dim=self.dim):
            t0 = time.perf_counter()
            shares = share_participants(secrets, key, self.plan, self.use_limbs)
            t1 = time.perf_counter()
            _step_hist("share").observe(t1 - t0)
            sums = clerk_combine_mod(shares, p)
            t2 = time.perf_counter()
            _step_hist("combine").observe(t2 - t1)
            if indices is None:
                indices = range(self.plan.share_count)
            out = reconstruct(sums, indices, self.scheme, self.dim)
            _step_hist("reconstruct").observe(time.perf_counter() - t2)
        return out

    # -- sharded paths -------------------------------------------------------

    def sharded_clerk_sums_all_to_all(self):
        """Clerk-sharded variant: the server-side transpose as an all_to_all.

        Where ``sharded_clerk_sums`` keeps participants sharded and psums
        per-clerk partials (bandwidth ~ n*B per device, replicated result),
        this variant physically reshards shares from participant-major to
        clerk-major over the ``p`` axis — the device-side realization of the
        snapshot transpose (server/src/snapshot.rs, SURVEY.md §3.2) — and
        each device then locally sums *all* participants for its own clerk
        slice. Right when clerks are many (n >= mesh size) and per-clerk
        downstream work (e.g. sealing results) should stay clerk-local.

        Returns fn(secrets_sharded, key) -> (n, B) clerk sums sharded over
        ``p`` on the clerk axis.
        """
        import jax
        from jax import lax
        from jax.sharding import PartitionSpec as P

        plan = self.plan
        use_limbs = self.use_limbs
        modulus = plan.modulus
        p_size = self.mesh.shape["p"]
        if plan.share_count % p_size != 0:
            raise ValueError(
                f"share_count {plan.share_count} must divide over mesh axis p={p_size}"
            )

        def local_step(secrets, key):
            key = fold_mesh_axes(key, self.mesh)
            shares = share_participants(secrets, key, plan, use_limbs)  # (Pl, n, B)
            # reshard: split the clerk axis across "p", gather participants —
            # afterwards each device holds (P_total_local_group, n/p, B)
            resharded = lax.all_to_all(
                shares, "p", split_axis=1, concat_axis=0, tiled=True
            )
            # all participants sum locally — wide-safe reduction
            return clerk_combine_mod(resharded, modulus)  # (n/p, B)

        mapped = jax.shard_map(
            local_step,
            mesh=self.mesh,
            in_specs=(P("p", None), P()),
            out_specs=P("p", None),
            check_vma=False,
        )
        return jax.jit(mapped)

    def _limb_accumulator_local_step(self, psum_axes):
        """Shared per-device body of the wide-modulus fabric: fused limb
        share+combine, then int64 partial psums over ``psum_axes`` in
        order (single-slice: ('p',); hybrid: ('p', 'h') — ICI before
        DCN). One definition so overflow-bound or chunking fixes apply to
        every fabric at once."""
        import jax
        from jax import lax

        plan = self.plan
        mesh = self.mesh

        def local_step(secrets, key):
            key = fold_mesh_axes(key, mesh)
            acc = share_combine_limb(secrets, key, plan)  # (W, b_local, n)
            with jax.named_scope("fabric.psum"):
                for ax in psum_axes:
                    acc = lax.psum(acc, axis_name=ax)
            return acc

        return local_step

    def validate_d_sharding(self, dim: int) -> None:
        """With a sharded dim axis every d-shard must hold whole batches;
        unsharded (d=1) keeps the usual zero-pad/truncate tail handling."""
        validate_d_sharding(self.mesh, dim, self.plan.input_size)

    def sharded_limb_accumulators(self):
        """Wide-modulus sharded fabric (BASELINE config 5 is 61-bit on
        v5e-8): each device runs the fused limb share+combine over its
        participant shard, partial accumulators psum over ``p`` — tiny
        ``(W, B, n)`` int64 tensors riding ICI — and the exact mod-p
        recombine of the reduced accumulator happens once on host
        (``limbmatmul.limb_recombine_host``), exactly like the epilogue of
        the single-chip rounds (``benchmark/rounds/packed_fold.py``).

        Exactness: per-device partials are bounded by ``C_local·L·K·127²``;
        the psum multiplies by the number of participant shards, so int64
        stays exact up to ~5e12 total participants — no rem needed on
        device at all.

        Returns fn(secrets_sharded, key) -> (W, B, n) int64 accumulators
        (replicated over ``p``, sharded over ``d`` on the B axis). Feed
        ``limb_recombine_host(acc, p).T`` then ``reconstruct``.
        """
        import jax
        from jax.sharding import PartitionSpec as P

        mapped = jax.shard_map(
            self._limb_accumulator_local_step(("p",)),
            mesh=self.mesh,
            # in_specs requires a "d" axis, so no d-less fallback here
            in_specs=(P("p", "d"), P()),
            out_specs=P(None, "d", None),
            check_vma=False,
        )
        return jax.jit(mapped)

    def sharded_clerk_sums(self):
        """Build the jitted sharded share+combine step over the mesh.

        Returns fn(secrets_sharded, key) -> (n, B) clerk sums (replicated
        over ``p``, sharded over ``d`` on the B axis).
        """
        import jax
        from jax import lax
        from jax.sharding import PartitionSpec as P

        jnp = _jnp()
        plan = self.plan
        use_limbs = self.use_limbs
        modulus = plan.modulus

        _check_psum_bound(self.mesh.shape["p"], modulus, "sharded_clerk_sums")

        def local_step(secrets, key):
            # per-device: share own participant slice, sum locally, psum.
            # every device folds all mesh coordinates into the key, so
            # every shard draws distinct randomness (see fold_mesh_axes)
            key = fold_mesh_axes(key, self.mesh)
            shares = share_participants(secrets, key, plan, use_limbs)
            partial = clerk_combine_mod(shares, modulus)  # (n, B_local)
            total = lax.psum(partial, axis_name="p")
            return lax.rem(total, jnp.int64(modulus))

        mapped = jax.shard_map(
            local_step,
            mesh=self.mesh,
            in_specs=(P("p", "d"), P()),
            out_specs=P(None, "d") if "d" in self.mesh.axis_names else P(),
            check_vma=False,
        )
        return jax.jit(mapped)



def _check_psum_bound(axis_size: int, modulus: int, where: str) -> None:
    """psum adds ``axis_size`` reduced partials (each in (-m, m)) in int64 —
    past ``axis_size*(m-1) < 2^63`` it silently wraps. Wide moduli must use
    the limb-accumulator fabrics instead, which psum small exact int64
    accumulators and recombine mod p once on host."""
    if axis_size * (modulus - 1) >= 2**63:
        raise ValueError(
            f"{where}: psum of {axis_size} partials overflows int64 at "
            f"modulus {modulus}; use sharded_limb_accumulators / "
            "hierarchical_limb_accumulators for wide moduli"
        )


def validate_d_sharding(mesh, dim: int, input_size: int) -> None:
    """With a sharded dim axis every d-shard zero-pads its own tail batch
    independently — non-divisible dims would misalign batch boundaries and
    silently reconstruct a wrong aggregate. One definition of the rule for
    every fabric (engine, multihost, sumfirst)."""
    d_size = mesh.shape.get("d", 1)
    if d_size > 1 and dim % (input_size * d_size) != 0:
        raise ValueError(
            f"dim {dim} must divide over input_size {input_size} x d={d_size} "
            "so every d-shard holds whole batches"
        )


def fold_mesh_axes(key, mesh):
    """Fold every mesh-axis index into the PRNG key (inside shard_map).

    Folding only one axis would hand devices that differ on another axis
    the SAME key: with the dim axis ``d`` sharded, two d-shards of one
    participant row would then draw identical share randomness for
    different dim slices — subtracting a clerk's shares across shards
    cancels it, a zero-privacy failure. Every sharded path (here and
    multihost.py) derives per-device randomness through this one helper.
    """
    import jax
    from jax import lax

    for axis in mesh.axis_names:
        key = jax.random.fold_in(key, lax.axis_index(axis))
    return key


def verified_step(agg, sums_fn):
    """Jitted round with verification handle: ``fn(secrets, key) ->
    (aggregate, plaintext-sum)`` — reconstruct from ``sums_fn``'s clerk
    sums plus an independent plaintext reduction of the same secrets.
    Shared by the single-mesh and multi-host (multihost.py) fabrics."""
    import jax

    jnp = _jnp()
    scheme, dim = agg.scheme, agg.dim

    def step(secrets, key):
        sums = sums_fn(secrets, key)
        out = reconstruct(sums, range(agg.plan.share_count), scheme, dim)
        from ..ops.modular import mod_sum_auto_jnp

        plain = mod_sum_auto_jnp(
            secrets.astype(jnp.int64), agg.plan.modulus, axis=0
        )
        return out, plain

    return jax.jit(step)


def full_training_step(scheme, dim, mesh):
    """One full secure-aggregation round as a single jitted computation:
    share + transpose + clerk-combine (sharded) then reconstruct + verify.

    This is the "training step" analog the driver dry-runs multi-chip.
    """
    agg = TpuAggregator(scheme, dim, mesh=mesh)
    return agg, verified_step(agg, agg.sharded_clerk_sums())
