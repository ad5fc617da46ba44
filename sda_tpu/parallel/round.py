"""The round driver: one object that carries a fold round's chunk step, its
accumulate rule, its epilogue and its reveal, on one chip or over the
deployment's mesh — and the host feed, which brings a round's rows from host
memory while the chip folds them.

A fabric round is ``accumulator -> chunk step over every chunk -> epilogue to
clerk sums -> reveal from a subset of clerks``. Which accumulate rule and
which host epilogue belong to a chunk entry is a property of the entry:

==========================================  =============  ==================================
chunk entry ``entry(secrets, key, plan)``   accumulate     host epilogue to ``(n, B)``
==========================================  =============  ==================================
``sumfirst.value_limb_sums_chunk``          ``sum``        ``sumfirst.clerk_sums_from_limb_acc``
``sumfirst.value_limb_sums_flat``           ``sum_words``  the same, the words joined first
``engine.share_combine_limb``               ``sum_mod_p``  ``limbmatmul.limb_recombine_host``
``engine.share_combine_limb_xla``           ``sum_mod_p``  ``limbmatmul.limb_recombine_host``
``limb_pallas.share_combine_limb_pallas``   ``sum_mod_p``  ``limbmatmul.limb_recombine_host``
==========================================  =============  ==================================

``sum``: exact integer limb sums, added with ``+`` (``sumfirst``'s bound of
2³¹ participants a round); ``sum_words``: the same sums held as uint32 words,
added with their carries (``sumfirst.add_limb_words``); ``sum_mod_p``: ``+``
then ``rem p``, so that the partials stay below p. :func:`fold_round` looks
the pair up from the entry (a ``functools.partial`` of an entry is that
entry), so no caller pairs them by hand. The driver adds no arithmetic: its
step is the entry on the round's key with the step's number folded in, then
the accumulate rule.

**The road of the epilogue and the reveal** is chosen by the accumulator's
size, not by a flag (:meth:`FoldRound.clerk_sums`). A sum-first accumulator
under ``epilogue.DEVICE_ROAD_BYTES`` (every 10⁵-wide cohort's, ≤ 3.2e6 B)
takes the host epilogue of the table and ``shamir.reconstruct_clerk_sums_host``;
one at or over it (a few fat rows of 10⁸ coordinates: 2.45e9 B) stays on the
chip: the device epilogue to ``(n, B)`` clerk sums that stay there, and the
device reveal, whose aggregate alone crosses to the host, as uint32 words
(``parallel/epilogue.py``). Both roads hand :meth:`FoldRound.reveal`'s caller
the same ``(dim,)`` int64 aggregate; ``sda_fabric_epilogue_roads_total{road}``
counts them.

**A masked round** (``fold_round(..., masking=ChaChaMasking(...))``). The
deployment's masking scheme is an argument of the round. Under it the step is
the mask stage in front of the paired entry (``masked.masked_chunk``: a fresh
seed a row from the step's key, the seed's ChaCha expansion added mod p), then
the entry's own accumulate rule, and it hands on more than an accumulator:
``masked_step(acc, chunk, key, i) -> (acc, seeds (chunk, words) uint32, counts
(chunk,) int32)``. The seeds and the counts do not accumulate by addition, so
both folds keep every step's, in step order, as the device arrays the steps
returned (20 bytes a row; nothing is fetched and nothing waits) and return
``(acc, seeds, counts)``. The rest of the round is the recipient's:
:meth:`FoldRound.short_windows` over the fetched counts (the slack check a
jitted step cannot make), :meth:`FoldRound.reveal` as it is (it reveals the
*masked* aggregate) and :meth:`FoldRound.unmask` from the seeds alone
(``crypto.masking.ChaChaMasker.combine`` / ``.unmask``). Bounds, spans and
counters of the feed are the same masked or not.

**A round over a mesh** (``fold_round(..., mesh=make_mesh(...))``). The
deployment's layout is an argument of the round as its masking scheme is: a
device mesh of axes ``("p", "d")``. ``chunk`` is then the rows a step folds on
all chips together, a multiple of the mesh's ``p``, and the step is the same
entry (the mask stage in front under ``masking=``) in the row-sharded wrapper
every fabric over a mesh shares (``engine.over_mesh``; the sum-first and the
limb fabric are built by it too): every chip takes its own ``(chunk / p, dim /
d)`` rows, the key with the step's number and then the chip's mesh position
folded in (``engine.fold_mesh_axes``), so no two chips draw the same share
randomness or the same seeds; the entry's accumulator is summed over ``p``
under ``fabric.psum``, then the accumulate rule. It hands the accumulator on
replicated over ``p``, and a masked step its ``(chunk, words)`` seeds and
``(chunk,)`` counts sharded over ``p``: they stay on the chips that drew them.
:meth:`FoldRound.fold_chunks` takes chunks that are sharded over the mesh
(``parallel.shard_participants``). A masked round's mesh keeps ``d = 1`` (a row
has one seed and its expansion runs the whole dim: ``ValueError``). The
recipient's side is spread over the same chips: :meth:`FoldRound.unmask` folds
``chunk`` seeds *a chip* a call, every chip expanding the seeds of its own
shard (``ops.chacha_pallas.fold_chunk_mesh_jit``: the one-chip fold on every
chip), and the chips' partial sums meet mod p under ``fabric.unmask/meet``
without leaving int64's range; the one combine loop of one chip and a mesh is
``ops.chacha_pallas.combine_masks_device``. Seeds handed over as host rows
(what a recipient receives) are put sharded; the device arrays the steps
returned are folded where they lie. The feed is one chip's:
:meth:`FoldRound.fold_host_rows` over a mesh raises ``ValueError``.

**The feed** (:meth:`FoldRound.fold_host_rows`). A cohort that does not fit
the chip's memory sits in host memory and crosses the host link every round.
The feed takes the round's rows as host blocks ``(block_rows, dim)``,
``block_rows`` a multiple of the chunk, and puts each block on the device
chunk by chunk: one ``jax.device_put`` of a ``(chunk, dim)`` row slice (a view
of the host block: no host copy, and no slicing program on the device), the
chunk's step dispatched behind it at once. Transfers and steps are
asynchronous: the link carries the next chunks while the chip folds the landed
one, and the runtime frees a chunk when its step has run and the feed has let
go of it. Two bounds hold the host back, and nothing else does:

* **the caller's, on memory**: a block is *alive* from its first put until
  the step that folds its last chunk has finished; before the first put of a
  block that would make more than ``in_flight`` blocks alive, the host waits
  for the oldest alive block's last step;
* **the link's own** (:data:`LINK_BYTES`): a chunk is *crossing* from its put
  until the feed has seen it landed; before a put that would make more than
  ``LINK_BYTES`` cross at once, the host waits for the oldest crossing chunk
  to land. The TPU runtime stages host transfers in a pinned pool of 4 GiB,
  and a transfer issued when the pool cannot hold it takes another road at
  0.2 GB/s instead of 10-12 (chip runs, PR 34: three 2.0e9 B blocks put back
  to back take 9.7 s where two take 0.35). The link is as busy with three
  chunks queued as with ten, so the bound keeps clear of the pool's edge.

The feed keeps its reference to a chunk only while it is crossing. Nothing is
kept from one call to the next: no block outlives the call, and nothing is
keyed on a block's identity or content.

Spans (``telemetry.span``, also a ``TraceAnnotation`` of the profiler's
trace): ``fabric.feed``, one a call of the feed (``in_flight``, and ``bytes``,
what the call put); inside it by time ``fabric.feed.put``, one a
``device_put`` call (``rows``, ``bytes``), ``fabric.feed.wait``, the host
blocked on a bound (``on``: ``in_flight`` or ``link``), and
``fabric.step.dispatch``, the host's call of the jitted step behind a put
(``step``: its number). The call less its puts, waits and step dispatches is
the host's own seconds: the slicing and the bookkeeping. ``fabric.feed`` also
says ``masked`` (whether the round masks). Counters:
``sda_fabric_fed_blocks_total``, ``sda_fabric_fed_rows_total``,
``sda_fabric_fed_bytes_total``, and ``sda_fabric_fed_seeds_total``, the seeds
the feed's steps handed on (the rows of masked steps; none unmasked); the gauge
``sda_fabric_feed_in_flight_max``: the most blocks alive at once in the last
call. The chunk step's device scopes (``fabric.input``, ``fabric.rand``, and
``fabric.mask`` in front of them in a masked round) are the entry's and the
mask stage's own.
"""

from __future__ import annotations

import collections
import dataclasses
import functools

import numpy as np

from .. import telemetry
from ..ops import shamir
from ..ops.jaxcfg import ensure_x64
from . import engine, epilogue, limb_pallas, limbmatmul, sumfirst

#: bytes the feed lets cross the host link at once (module doc): three quarters
#: of the runtime's staging pool. Measured on a v5e (chip runs, PR 34; a round
#: of 8.0e9 B, eight rounds a bound): 0.63 s at 1.2e9, 0.64 at 2.0e9, 0.65 at
#: 3.2e9, none slow; at 4.0e9 one round in eight to twenty takes 2.3 s (a chunk
#: off the pool). The most that stays clear of the pool's edge: the more is
#: queued, the longer the host may be away before the link runs dry
LINK_BYTES = 3_200_000_000


class _Crossing:
    """The chunks put on the device and not yet seen landed, oldest first,
    held to ``limit`` bytes. Holds the feed's only reference to a chunk."""

    def __init__(self, limit: int):
        self.limit, self.nbytes, self.chunks = limit, 0, collections.deque()

    def make_room(self, nbytes: int) -> None:
        """Let go of the chunks that have landed, and wait for the oldest
        others to land until ``nbytes`` more may cross."""
        chunks = self.chunks
        while chunks and (chunks[0].is_ready() or self.nbytes + nbytes > self.limit):
            oldest = chunks.popleft()
            if not oldest.is_ready():
                with telemetry.span("fabric.feed.wait", on="link"):
                    oldest.block_until_ready()
            self.nbytes -= oldest.nbytes

    def add(self, chunk) -> None:
        self.chunks.append(chunk)
        self.nbytes += chunk.nbytes


def _limb_acc_epilogue(acc, plan):
    return sumfirst.clerk_sums_from_limb_acc(acc, plan)[0]


def _flat_limb_acc_epilogue(acc, plan):
    return _limb_acc_epilogue(sumfirst.batched_limb_acc(acc, plan), plan)


def _limb_recombine_epilogue(acc, plan):
    return limbmatmul.limb_recombine_host(acc, plan.modulus).T


#: a chunk entry's accumulate rule and its host epilogue (module doc)
_PAIRED = {
    sumfirst.value_limb_sums_chunk: ("sum", _limb_acc_epilogue),
    sumfirst.value_limb_sums_flat: ("sum_words", _flat_limb_acc_epilogue),
    engine.share_combine_limb: ("sum_mod_p", _limb_recombine_epilogue),
    engine.share_combine_limb_xla: ("sum_mod_p", _limb_recombine_epilogue),
    limb_pallas.share_combine_limb_pallas: ("sum_mod_p", _limb_recombine_epilogue),
}


#: the entries whose accumulator the device epilogue takes (module doc)
_SUM_FIRST = (sumfirst.value_limb_sums_chunk, sumfirst.value_limb_sums_flat)


def _input_dtype(modulus: int):
    """int32 where every canonical value fits it, else int64: the type the
    engines keep the big tensor in."""
    return np.dtype(np.int32 if modulus <= (1 << 31) else np.int64)


def _make_step(entry, plan, accumulate: str, masking=None, mesh=None):
    """The jitted ``step(acc, chunk, key, i) -> acc``; under ``masking`` the
    jitted ``masked_step(acc, chunk, key, i) -> (acc, seeds, counts)``: the
    same step with the mask stage in front of the entry, the rows' seeds and
    accepted-draw counts leaving it beside the accumulator. Over a ``mesh``
    the entry (and the mask stage) run on every chip's own rows
    (``engine.over_mesh``); the accumulate rule is the same."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    modulus = plan.modulus

    def accumulated(acc, out):
        if accumulate == "sum_words":
            return sumfirst.add_limb_words(acc, out)
        acc = acc + out
        if accumulate == "sum_mod_p":
            acc = lax.rem(acc, jnp.int64(modulus))
        return acc

    chunk_fn = lambda chunk, key: entry(chunk, key, plan)
    if masking is not None:
        # imported where a masked round is built: an unmasked round's set-up
        # does not pay for the mask stage's modules
        from .masked import masked_chunk

        chunk_fn = masked_chunk(entry, plan, masking)  # refuses a scheme that is not the plan's
    if mesh is not None:
        chunk_fn = engine.over_mesh(chunk_fn, plan, mesh, masked=masking is not None)

    def step(acc, chunk, key, i):
        # one chunk step: the round's key with the step's number folded in,
        # the entry's default share randomness, the accumulate rule
        return accumulated(acc, chunk_fn(chunk, jax.random.fold_in(key, i)))

    def masked_step(acc, chunk, key, i):
        out, seeds, counts = chunk_fn(chunk, jax.random.fold_in(key, i))
        return accumulated(acc, out), seeds, counts

    return jax.jit(step if masking is None else masked_step)


@dataclasses.dataclass(frozen=True)
class FoldRound:
    """One fold round of a scheme at a dim and a chunk size: the jitted chunk
    step, the zero accumulator, the folds (of resident chunks, of host
    blocks), the epilogue and the reveal on either road; under a masking
    scheme also the slack check and the unmasking. Built by
    :func:`fold_round`; holds no array."""

    scheme: object
    plan: engine.AggregationPlan
    chunk: int  # rows a chunk step folds
    entry: object  # entry(secrets, key, plan) -> accumulator, one chunk
    accumulate: str  # "sum" | "sum_words" | "sum_mod_p"
    acc_shape: tuple  # of the accumulator a step takes and hands on
    #: jitted step(acc, chunk, key, i) -> acc; under a masking scheme
    #: masked_step(acc, chunk, key, i) -> (acc, seeds, counts)
    step: object
    epilogue: object  # fn(acc_host, plan) -> (n, B) clerk sums
    masking: object = None  # the round's protocol.ChaChaMasking, or None
    mesh: object = None  # the deployment's device mesh, axes ("p", "d"), or None
    acc_dtype: str = "int64"  # uint32 for the flat entry's words

    @property
    def modulus(self) -> int:
        return self.plan.modulus

    @property
    def input_dtype(self):
        return _input_dtype(self.modulus)

    def zero_acc(self):
        import jax
        import jax.numpy as jnp

        acc = jnp.zeros(self.acc_shape, self.acc_dtype)
        if self.mesh is None:
            return acc
        from jax.sharding import NamedSharding

        # on every chip before the first step, as every step hands it on
        return jax.device_put(acc, NamedSharding(self.mesh, engine.mesh_acc_spec(self.mesh)))

    def _stepped(self, acc, chunk, key, number: int, handed: list):
        """The accumulator after step ``number`` over ``chunk``; what a
        masked step hands on beside it joins ``handed``."""
        out = self.step(acc, chunk, key, np.int32(number))
        if self.masking is None:
            return out
        acc, seeds, counts = out
        handed.append((seeds, counts))
        return acc

    def _folded(self, acc, handed: list):
        """What a fold returns: the accumulator, and under a masking scheme
        the steps' seeds and counts beside it, in step order."""
        if self.masking is None:
            return acc
        return acc, [seeds for seeds, _ in handed], [counts for _, counts in handed]

    def fold_chunks(self, chunks, key):
        """The accumulator of ``chunks`` (``(chunk, dim)`` arrays, resident
        or not; over a mesh sharded over it, rows over ``p``), step ``i`` over
        the ``i``-th of them. Under a masking scheme ``(acc, seeds, counts)``:
        every step's ``(chunk, words)`` uint32 seeds and ``(chunk,)`` int32
        counts, in step order, as the device arrays the steps returned (over
        a mesh sharded over ``p``: on the chips that drew them)."""
        acc, handed = self.zero_acc(), []
        for i, chunk in enumerate(chunks):
            acc = self._stepped(acc, chunk, key, i, handed)
        return self._folded(acc, handed)

    def fold_host_rows(self, blocks, key, *, in_flight: int):
        """The accumulator of the rows of ``blocks``, host arrays ``(rows,
        dim)`` of :attr:`input_dtype` with ``rows`` a multiple of
        :attr:`chunk`, at most ``in_flight`` of them alive on the device at
        once and at most :data:`LINK_BYTES` crossing the link (module doc).
        Returns with the last transfers and steps still running: the caller's
        ``block_until_ready`` on the accumulator is the wait for them. The
        same as :meth:`fold_chunks` over the same rows in the same order,
        masked or not: under a masking scheme ``(acc, seeds, counts)``."""
        if self.mesh is not None:
            raise ValueError(
                "the feed is one chip's: over a mesh, fold chunks that are sharded over it "
                "(fold_chunks)"
            )

        if in_flight < 1:
            raise ValueError("in_flight counts blocks: at least 1")
        # the program's own `dispatch`: the puts, the waits and the step
        # dispatches nest inside it by time, and what is left of it is the
        # host's own seconds
        masked = self.masking is not None
        with telemetry.span("fabric.feed", in_flight=in_flight, masked=masked) as call:
            folded, nbytes = self._feed(blocks, key, in_flight)
            if call is not None:  # telemetry is on
                call["attrs"]["bytes"] = nbytes
        return folded

    def _feed(self, blocks, key, in_flight: int):
        """What :meth:`fold_host_rows` returns, and the bytes it put."""
        import jax

        fed_blocks = telemetry.counter(
            "sda_fabric_fed_blocks_total", "host blocks the feed put on the device"
        )
        fed_rows = telemetry.counter("sda_fabric_fed_rows_total", "rows the feed put on the device")
        fed_bytes = telemetry.counter(
            "sda_fabric_fed_bytes_total", "bytes the feed put on the device"
        )
        fed_seeds = telemetry.counter(
            "sda_fabric_fed_seeds_total", "seeds the feed's masked steps handed on"
        )
        acc, handed = self.zero_acc(), []
        # the accumulator after each alive block's last step, oldest first:
        # when it is ready, that block's chunks have been folded
        alive = collections.deque()
        crossing = _Crossing(LINK_BYTES)
        steps = most = nbytes = 0
        for block in blocks:
            block = self._checked(block)
            while len(alive) >= in_flight:
                with telemetry.span("fabric.feed.wait", on="in_flight"):
                    alive.popleft().block_until_ready()
            most = max(most, len(alive) + 1)
            for start in range(0, block.shape[0], self.chunk):
                rows = block[start : start + self.chunk]
                crossing.make_room(rows.nbytes)
                with telemetry.span("fabric.feed.put", rows=self.chunk, bytes=rows.nbytes):
                    chunk = jax.device_put(rows)
                crossing.add(chunk)
                with telemetry.span("fabric.step.dispatch", step=steps):
                    acc = self._stepped(acc, chunk, key, steps, handed)
                del chunk  # the feed's reference is the crossing's alone
                steps += 1
            fed_blocks.inc()
            fed_rows.inc(block.shape[0])
            fed_bytes.inc(block.nbytes)
            fed_seeds.inc(block.shape[0] if self.masking is not None else 0)
            nbytes += block.nbytes
            alive.append(acc)
        telemetry.gauge(
            "sda_fabric_feed_in_flight_max", "most blocks alive at once in the feed's last call"
        ).set(most)
        return self._folded(acc, handed), nbytes

    def _checked(self, block):
        block = np.asarray(block)
        if block.ndim != 2 or block.shape[1] != self.plan.dim:
            raise ValueError(f"a block is (rows, {self.plan.dim}), not {block.shape}")
        if block.shape[0] == 0 or block.shape[0] % self.chunk:
            raise ValueError(
                f"a block's rows are a positive multiple of the chunk, {self.chunk}: "
                f"not {block.shape[0]}"
            )
        if block.dtype != self.input_dtype:
            raise ValueError(f"a block is {self.input_dtype}, not {block.dtype}")
        return block

    @functools.cached_property
    def epilogue_program(self):
        """The device road's jitted ``device_clerk_sums(word_acc) -> (n,
        B)`` (``epilogue.clerk_sums_program``)."""
        return epilogue.clerk_sums_program(self.plan)

    @functools.cached_property
    def reveal_program(self):
        """The device road's jitted ``device_reveal(clerk_sums, rows,
        lagrange) -> words`` (``epilogue.reveal_program``)."""
        return epilogue.reveal_program(self.plan)

    def clerk_sums(self, acc):
        """The accumulator (on the device or fetched) -> ``(n, B)`` int64
        canonical clerk sums, by the road its size picks (module doc): a
        host array from the host epilogue, or, for a sum-first accumulator
        of ``epilogue.DEVICE_ROAD_BYTES`` or more, a device array from the
        device epilogue. Counted in ``sda_fabric_epilogue_roads_total``."""
        sum_first = getattr(self.entry, "func", self.entry) in _SUM_FIRST
        if sum_first and epilogue.device_road(acc.nbytes):
            epilogue.count_road("device")
            return self.epilogue_program(epilogue.word_limb_acc(acc, self.plan))
        epilogue.count_road("host")
        return np.asarray(self.epilogue(np.asarray(acc), self.plan))

    def reveal(self, clerk_sums, clerks):
        """The ``(dim,)`` canonical int64 aggregate on the host from the sums
        of the 0-based ``clerks`` (at least ``reconstruction_threshold`` of
        them), on the road the clerk sums came by: host clerk sums by
        ``shamir.reconstruct_clerk_sums_host``, device ones by the device
        reveal (``epilogue.reveal``: the aggregate fetched as its uint32
        words, low then high, and viewed as int64).
        In a masked round the *masked* aggregate, which :meth:`unmask` takes
        the masks off."""
        import jax

        if isinstance(clerk_sums, jax.Array):
            lagrange = epilogue.lagrange_operand(self.scheme, clerks)
            rows = np.asarray(clerks, dtype=np.int32)
            return epilogue.fetch_aggregate(self.reveal_program(clerk_sums, rows, lagrange))
        out = shamir.reconstruct_clerk_sums_host(clerk_sums, clerks, self.scheme, self.plan.dim)
        return np.mod(np.asarray(out).astype(np.int64), self.modulus)

    def short_windows(self, counts) -> int:
        """The slack check of a masked round, over the steps' fetched
        ``counts`` (one array a step): how many rows' keystream
        windows held fewer than ``dim`` accepted draws. A round with any must
        not be revealed (``masked.count_short_windows`` counts them)."""
        from .masked import count_short_windows

        self._masked_only()
        return count_short_windows(np.concatenate([np.ravel(c) for c in counts]), self.plan.dim)

    def unmask(self, masked_aggregate, seeds, *, chunk: int | None = None):
        """The ``(dim,)`` canonical int64 aggregate of a masked round: what
        :meth:`reveal` gave less the sum of the masks, which the recipient
        re-expands from ``seeds`` alone (as it receives them: one vector of
        the seed's uint32 words a participant, every row's, any order);
        ``chunk`` seeds a device fold (``ChaChaMasker.combine``). Over a mesh
        the fold runs on every chip of it, ``chunk`` seeds *a chip* a fold,
        and the chips' partial sums meet mod p (``fabric.unmask/meet``):
        seeds handed over as host rows are put sharded; the ``(rows, words)``
        device arrays the steps returned, sharded over the mesh, are folded
        where they lie, every chip expanding the seeds it drew."""
        from ..crypto.masking import new_mask_combiner

        self._masked_only()
        masker = new_mask_combiner(self.masking)  # the recipient's ChaChaMasker
        mask = masker.combine(seeds, chunk=chunk, mesh=self.mesh)
        return np.mod(masker.unmask(mask, masked_aggregate), self.modulus)

    def _masked_only(self) -> None:
        if self.masking is None:
            raise ValueError("the round has no masking scheme: nothing to check or take off")


def fold_round(scheme, dim: int, entry, chunk: int, masking=None, mesh=None) -> FoldRound:
    """The round of ``scheme`` at ``dim`` through the chunk entry ``entry``
    (``entry(secrets, key, plan) -> accumulator``, one of the module doc's
    table, or a ``functools.partial`` of one), ``chunk`` rows a step; under
    ``masking`` (a ``protocol.ChaChaMasking`` of the plan's modulus and
    dimension) the masked round of the module doc, whatever the entry's
    accumulate rule; over ``mesh`` (the deployment's layout: a device mesh of
    axes ``("p", "d")``, ``parallel.make_mesh``) the round over the mesh of
    the module doc, ``chunk`` the rows a step folds on all chips together."""
    ensure_x64()
    paired = _PAIRED.get(getattr(entry, "func", entry))
    if paired is None:
        raise ValueError(
            f"no accumulate rule and epilogue are known for the chunk entry {entry!r}"
        )
    if chunk < 1:
        raise ValueError("a chunk holds at least one row")
    if mesh is not None and chunk % mesh.shape["p"]:
        raise ValueError(
            f"a chunk's rows spread evenly over the mesh's p = {mesh.shape['p']}: not {chunk}"
        )
    if mesh is not None and getattr(entry, "func", entry) is sumfirst.value_limb_sums_flat:
        raise ValueError("the flat accumulator is one chip's: over a mesh, the batched entry")
    if mesh is not None and masking is not None and mesh.shape.get("d", 1) > 1:
        raise ValueError(
            "a masked round's mesh keeps d = 1: a row has one seed and its expansion runs "
            "the whole dim, so the mask stage has no dim shard of its own"
        )
    import jax

    accumulate, epilogue = paired
    plan = engine.make_plan(scheme, dim)
    # the accumulator of all chips' rows is as wide as one chip's: the entry's
    # at the whole dim (over a mesh a chip holds its d-shard's batches of it)
    acc = jax.eval_shape(
        lambda rows, key: entry(rows, key, plan),
        jax.ShapeDtypeStruct((chunk, dim), _input_dtype(plan.modulus)),
        jax.eval_shape(lambda: jax.random.key(0)),
    )
    return FoldRound(
        scheme=scheme,
        plan=plan,
        chunk=int(chunk),
        entry=entry,
        accumulate=accumulate,
        acc_shape=tuple(acc.shape),
        acc_dtype=str(acc.dtype),
        step=_make_step(entry, plan, accumulate, masking, mesh),
        epilogue=epilogue,
        masking=masking,
        mesh=mesh,
    )
