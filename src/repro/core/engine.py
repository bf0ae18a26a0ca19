"""Communication engines: the "software GASNet node" vs the "GAScore".

The paper's central demonstration is that software nodes (x86/ARM GASNet)
and hardware nodes (the GAScore remote-DMA engine) interoperate through one
API.  We reproduce that split exactly:

- :class:`XlaEngine`     — the *software node*: transport primitives are
  ``jax.lax`` collectives, i.e. XLA's own (reference) implementation.
- :class:`GascoreEngine` — the *hardware node*: the same primitives are the
  hand-written Pallas remote-DMA kernels from ``repro.kernels.gascore``
  (``pltpu.make_async_remote_copy`` + DMA semaphores over ICI).

Both expose the identical :class:`CommEngine` interface, so any code built
on top (the ring collectives, the AM router, user programs) migrates from
software to hardware by swapping the engine — the paper's software→hardware
migration story with zero API change.

All methods must be called inside a ``shard_map`` over ``self.axis``.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

__all__ = [
    "CommEngine",
    "Pending",
    "AlreadyWaitedError",
    "wait_all",
    "XlaEngine",
    "GascoreEngine",
    "EngineMap",
    "make_engine",
    "parse_backend_spec",
]


def ring_pairs(n: int, k: int) -> List[Tuple[int, int]]:
    """Permutation pairs for 'every node sends to (me + k) mod n'."""
    k = k % n
    return [(i, (i + k) % n) for i in range(n)]


class AlreadyWaitedError(RuntimeError):
    """A split-phase handle was synced twice.

    A transfer completes exactly once (``gasnet_wait_syncnb`` semantics);
    the message always names the offending op so batch waits
    (:func:`wait_all`, ``node.sync_all``) are debuggable.
    """


class Pending:
    """An in-flight transport operation (the engine half of split-phase).

    ``shift_nb``/``permute_nb`` return a ``Pending`` at *initiation*;
    ``wait()`` is the *sync point* that yields the delivered value.  Any
    compute traced between initiation and ``wait()`` has no data dependence
    on the transfer, so the scheduler is free to overlap it:

    - ``XlaEngine``: the ppermute lowers to an async ``collective-permute``
      start/done pair; XLA's latency-hiding scheduler slides independent
      compute between them (double-buffered scheduling).
    - ``GascoreEngine``: the Pallas kernel's DMA *recv-semaphore wait* is
      the sync point; the DMA itself progresses in the background exactly
      like the paper's GAScore engine draining its command FIFO.

    ``op`` labels the operation for error messages (``shift(k=1)``,
    ``permute``, ...), so a double-wait — including one buried inside a
    :func:`wait_all` batch — names the op instead of raising bare.
    """

    __slots__ = ("_value", "_waited", "op")

    def __init__(self, value: jax.Array, op: str = "transfer"):
        self._value = value
        self._waited = False
        self.op = op

    @property
    def waited(self) -> bool:
        return self._waited

    def wait(self) -> jax.Array:
        """Complete the transfer and return the delivered value (a
        transfer completes exactly once, like ``gasnet_wait_syncnb``)."""
        if self._waited:
            raise AlreadyWaitedError(
                f"Pending {self.op} transfer already waited on"
            )
        self._waited = True
        return self._value

    def ready(self) -> bool:
        """Poll (``gasnet_try_syncnb``).  The static SPMD schedule
        guarantees delivery of every initiated transfer, so this is
        constant-``True`` — kept for API fidelity."""
        return True


def wait_all(pendings: Sequence["Pending"]) -> List[jax.Array]:
    """Complete a batch of pendings in issue order (``gasnet_wait_syncnb_all``).

    Idempotence is checked up front: if any entry was already waited on,
    raise one clear error naming the op and its position *before* consuming
    any of the others, so the batch is not left half-drained.
    """
    stale = [
        (i, p.op) for i, p in enumerate(pendings) if p.waited
    ]
    if stale:
        desc = ", ".join(f"#{i} ({op})" for i, op in stale)
        raise AlreadyWaitedError(
            f"wait_all: pending transfer(s) already waited on: {desc}"
        )
    return [p.wait() for p in pendings]


class CommEngine:
    """Transport primitives of one GASNet node.

    ``axis`` is the mesh axis enumerating the nodes; ``n_nodes`` its size.

    ``can_permute_partial`` advertises whether :meth:`permute` accepts
    ``None`` destinations (nodes that send nowhere).  The XLA transport
    can (a chain collective-permute); the GAScore transport cannot — every
    recv semaphore must be signalled exactly once, so only bijections are
    legal.  The scheduler consults this instead of engine ``isinstance``
    checks when choosing tree vs ring algorithms and chain vs ring
    pipeline boundaries.
    """

    name = "abstract"
    can_permute_partial = False

    def __init__(self, axis: str, n_nodes: int):
        self.axis = axis
        self.n_nodes = n_nodes

    def backend_of(self, rank: int) -> str:
        """Backend name serving ``rank`` (uniform for homogeneous engines;
        :class:`EngineMap` overrides per rank)."""
        return self.name

    # -- point-to-point (one-sided put transport) ----------------------- #
    def shift(self, x: jax.Array, k: int = 1) -> jax.Array:
        """Every node's ``x`` lands on node ``(me + k) % n``."""
        raise NotImplementedError

    def permute(self, x: jax.Array, dst: Sequence[int]) -> jax.Array:
        """Static permutation: node i's ``x`` lands on node ``dst[i]``.
        Non-destinations receive zeros."""
        raise NotImplementedError

    # -- split-phase point-to-point (Extended API transport) ------------- #
    def shift_nb(self, x: jax.Array, k: int = 1) -> Pending:
        """Non-blocking :meth:`shift`: initiate the transfer of ``x`` to
        node ``(me + k) % n`` and return a :class:`Pending` whose
        ``wait()`` is the sync point.  Compute traced between the two
        overlaps with the transfer."""
        return Pending(self.shift(x, k), op=f"shift(k={k})")

    def permute_nb(self, x: jax.Array, dst: Sequence[int]) -> Pending:
        """Non-blocking :meth:`permute` (split-phase, see :meth:`shift_nb`)."""
        return Pending(self.permute(x, dst), op="permute")

    # -- vectored split-phase transport (engine-level multi-get/multi-put) #
    def _pack_nbv(self, xs: Sequence[jax.Array]) -> jax.Array:
        flats = [x.reshape(-1) for x in xs]
        dtypes = {f.dtype for f in flats}
        if len(dtypes) > 1:
            raise TypeError(
                f"vectored transfer payloads must share one dtype, got "
                f"{sorted(str(d) for d in dtypes)}"
            )
        return flats[0] if len(flats) == 1 else jnp.concatenate(flats)

    def _unpack_nbv(
        self, moved: jax.Array, xs: Sequence[jax.Array], op: str
    ) -> List[Pending]:
        out: List[Pending] = []
        offset = 0
        for x in xs:
            piece = moved[offset : offset + x.size].reshape(x.shape)
            out.append(Pending(piece, op=op))
            offset += x.size
        return out

    def shift_nbv(self, xs: Sequence[jax.Array], k: int = 1) -> List[Pending]:
        """Vectored non-blocking shift: ONE transport initiation (a single
        command word / DMA descriptor) carries every payload in ``xs`` to
        node ``(me + k) % n``; returns one :class:`Pending` per payload.

        This is the engine half of a multi-get/multi-put: m transfers cost
        one initiation α instead of m — the GAScore draining a whole FIFO
        of commands as one wire message.  Payloads must share a dtype (the
        carrier); sizes are static so the receive split is free.
        """
        xs = list(xs)
        if not xs:
            return []
        moved = self.shift(self._pack_nbv(xs), k)
        return self._unpack_nbv(moved, xs, op=f"shiftv(k={k})")

    def permute_nbv(
        self, xs: Sequence[jax.Array], dst: Sequence[int]
    ) -> List[Pending]:
        """Vectored non-blocking :meth:`permute` (see :meth:`shift_nbv`)."""
        xs = list(xs)
        if not xs:
            return []
        moved = self.permute(self._pack_nbv(xs), dst)
        return self._unpack_nbv(moved, xs, op="permutev")

    # -- vectored put transport (payloads + command block in one message) - #
    def _nbv_put(
        self, mover, xs: Sequence[jax.Array], meta: jax.Array
    ) -> Tuple[List[Pending], Pending]:
        xs = list(xs)
        if not xs:
            raise ValueError("vectored put needs at least one payload")
        meta = jnp.asarray(meta, jnp.int32).reshape(-1)
        if jnp.dtype(xs[0].dtype).itemsize == 4:
            # the int32 command block bitcasts into the payload carrier, so
            # payloads AND their target offsets ride ONE transport
            # initiation — the GAScore draining a whole command FIFO as a
            # single wire message.
            mcarrier = lax.bitcast_convert_type(meta, xs[0].dtype)
            pendings = mover(xs + [mcarrier])
            return pendings[:-1], pendings[-1]
        # non-4-byte carriers: the command block rides its own initiation
        # (still 2 α for m puts instead of 3m).
        payload = mover(xs)
        (mp,) = mover([meta])
        return payload, mp

    def shift_nbv_put(
        self, xs: Sequence[jax.Array], meta: jax.Array, k: int = 1
    ) -> Tuple[List[Pending], Pending]:
        """Vectored put transport to node ``(me + k) % n``: the write-side
        mirror of :meth:`shift_nbv`.  ``xs`` are the m payload vectors and
        ``meta`` the int32 *command block* (target offsets + arrival
        flags) — shipped together in one initiation when the payload dtype
        is 4 bytes wide (the command words bitcast into the carrier).
        Returns ``(payload_pendings, meta_pending)``; the meta pending
        completes to the carrier dtype and the caller bitcasts it back.
        """
        return self._nbv_put(lambda v: self.shift_nbv(v, k), xs, meta)

    def permute_nbv_put(
        self, xs: Sequence[jax.Array], meta: jax.Array, dst: Sequence[int]
    ) -> Tuple[List[Pending], Pending]:
        """Vectored put transport along a permutation (see
        :meth:`shift_nbv_put`)."""
        return self._nbv_put(lambda v: self.permute_nbv(v, dst), xs, meta)

    # -- collectives ----------------------------------------------------- #
    def all_to_all(self, x: jax.Array) -> jax.Array:
        """x: (n_nodes * m, ...) tiled exchange along dim 0.

        Default implementation: the fully overlapped split-phase exchange
        (all n-1 one-sided puts initiated before any completion is
        consumed, see ``collectives.exchange``).  Engines with a native
        all-to-all (XLA) override this."""
        # lazy import, mirroring _k(): collectives imports this module.
        from repro.core import collectives

        return collectives.exchange(self, x)

    def all_gather(self, x: jax.Array) -> jax.Array:
        """x: local (m, ...) -> (n_nodes * m, ...)."""
        raise NotImplementedError

    def reduce_scatter(self, x: jax.Array) -> jax.Array:
        """x: (n_nodes * m, ...) -> summed local (m, ...)."""
        raise NotImplementedError

    def all_reduce(self, x: jax.Array) -> jax.Array:
        raise NotImplementedError

    # -- control ---------------------------------------------------------- #
    def my_id(self) -> jax.Array:
        return lax.axis_index(self.axis)

    def barrier(self, token: jax.Array | None = None) -> jax.Array:
        """GASNet barrier.  In bulk-synchronous SPMD a barrier is implied by
        any collective; we keep the call for API fidelity and as an
        explicit synchronization edge (psum of a unit token)."""
        t = jnp.ones((), jnp.int32) if token is None else token
        return lax.psum(t, self.axis)


class XlaEngine(CommEngine):
    """Software GASNet node: XLA collectives as the transport."""

    name = "xla"
    can_permute_partial = True

    def shift(self, x: jax.Array, k: int = 1) -> jax.Array:
        if k % self.n_nodes == 0:
            return x
        return lax.ppermute(x, self.axis, ring_pairs(self.n_nodes, k))

    def permute(self, x: jax.Array, dst: Sequence[int]) -> jax.Array:
        pairs = [(i, int(d)) for i, d in enumerate(dst) if d is not None]
        return lax.ppermute(x, self.axis, pairs)

    def all_to_all(self, x: jax.Array) -> jax.Array:
        return lax.all_to_all(x, self.axis, split_axis=0, concat_axis=0, tiled=True)

    def all_gather(self, x: jax.Array) -> jax.Array:
        return lax.all_gather(x, self.axis, tiled=True)

    def reduce_scatter(self, x: jax.Array) -> jax.Array:
        return lax.psum_scatter(x, self.axis, scatter_dimension=0, tiled=True)

    def all_reduce(self, x: jax.Array) -> jax.Array:
        return lax.psum(x, self.axis)


class GascoreEngine(CommEngine):
    """Hardware GASNet node: Pallas remote-DMA (GAScore) as the transport.

    The kernels compile to Mosaic over ICI on a TPU and run in
    TPU-interpret mode (CPU emulation of DMAs + semaphores) on the CPU.
    """

    name = "gascore"

    # kernels are imported lazily to keep `repro.core` import-light and to
    # avoid a core <-> kernels import cycle.
    def _k(self):
        from repro.kernels import gascore

        return gascore

    def shift(self, x: jax.Array, k: int = 1) -> jax.Array:
        if k % self.n_nodes == 0:
            return x
        return self._k().ring_shift(
            x, k=k, axis=self.axis, n_nodes=self.n_nodes
        )

    def permute(self, x: jax.Array, dst: Sequence[int]) -> jax.Array:
        return self._k().perm_put(
            x, dst=tuple(int(d) for d in dst), axis=self.axis,
            n_nodes=self.n_nodes,
        )

    def all_gather(self, x: jax.Array) -> jax.Array:
        return self._k().ring_all_gather(
            x, axis=self.axis, n_nodes=self.n_nodes
        )

    def reduce_scatter(self, x: jax.Array) -> jax.Array:
        return self._k().ring_reduce_scatter(
            x, axis=self.axis, n_nodes=self.n_nodes
        )

    def all_reduce(self, x: jax.Array) -> jax.Array:
        # RS + AG when the leading dim tiles evenly; otherwise a shift-and-
        # accumulate ring (n-1 hops carrying the full tensor).
        lead = x.shape[0] if x.ndim else 0
        if x.ndim and lead % self.n_nodes == 0 and lead > 0:
            return self.all_gather(self.reduce_scatter(x))
        acc = x
        cur = x
        for _ in range(self.n_nodes - 1):
            cur = self.shift(cur, 1)
            acc = acc + cur
        return acc

    # all_to_all: inherited split-phase exchange over shift_nb (each of the
    # n-1 remote DMAs is in flight before any recv-semaphore wait).


class EngineMap(CommEngine):
    """Heterogeneous node map: each rank is backed by its own engine.

    The paper's cluster mixes software nodes (x86/ARM GASNet) and hardware
    nodes (GAScore) in one job; here ``backends[r]`` names the engine
    serving rank ``r`` (``"xla"`` = software node, ``"gascore"`` = hardware
    node).  Point-to-point transport is carried *per edge* by the sender's
    engine: every member engine moves the payload (all ranks participate in
    both transports — the SPMD analogue of a packet crossing engine
    domains), and each receiver keeps the copy delivered by its sender's
    backend.  Collectives are the ring/put algorithms from
    ``repro.core.collectives`` running over that mixed edge transport, so
    mixed jobs run unmodified — and match both homogeneous engines bit for
    bit (parity is asserted in the testing suites).

    A partial permute (``None`` destinations) is only legal when every
    member engine supports it.
    """

    name = "map"

    def __init__(
        self,
        axis: str,
        backends: Sequence[str],
        engines: dict | None = None,
    ):
        super().__init__(axis, len(backends))
        self.backends = tuple(backends)
        uniq: List[str] = []
        for b in self.backends:
            if b not in uniq:
                uniq.append(b)
        if engines is None:
            engines = {
                b: _make_single_engine(b, axis, self.n_nodes)
                for b in uniq
            }
        self._engines = engines
        self._uniq = tuple(uniq)
        # bool mask per backend: which ranks it serves (host constants)
        self._masks = {
            b: jnp.asarray([be == b for be in self.backends])
            for b in self._uniq
        }
        self.can_permute_partial = all(
            self._engines[b].can_permute_partial for b in self._uniq
        )
        # (trace, output) of the map's last transport (see _each)
        self._last = None

    def backend_of(self, rank: int) -> str:
        return self.backends[rank % self.n_nodes]

    def member(self, backend: str) -> CommEngine:
        return self._engines[backend]

    @property
    def is_heterogeneous(self) -> bool:
        return len(self._uniq) > 1

    # -- per-edge transport selection ----------------------------------- #
    def _each(self, move, x: jax.Array) -> dict:
        """``move(engine, x)`` on every member engine.

        A GAScore kernel blocks until every peer has entered it, and an
        XLA collective until every peer has joined it.  On a TPU every
        device runs the program's operations in one order.  The CPU
        runtime (interpret mode) runs independent operations concurrently,
        so one device can sit in an XLA collective while its peers sit in
        a GAScore kernel, and all wait forever.  There, each transport's
        input is made to depend on the output of the map's previous
        transport in the same trace, across calls too: the segmented ring
        issues independent shifts back to back.  The dependence is a
        select on ``me >= n``, which never holds but which the compiler
        cannot prove; the CPU compiler drops an ``optimization_barrier``
        and the dependence with it.  The map keeps that output until its
        next transport; a transport in another trace (a loop body, a later
        jit) starts a new chain."""
        from repro.kernels import mode

        if mode.on_tpu():
            return {b: move(self._engines[b], x) for b in self._uniq}
        state = jax.core.get_opaque_trace_state()
        prev = self._last[1] if self._last and self._last[0] == state else None
        outs = {}
        for b in self._uniq:
            if prev is not None:
                after = jnp.ravel(prev)[0].astype(x.dtype)
                x = jnp.where(self.my_id() >= self.n_nodes, after, x)
            outs[b] = prev = move(self._engines[b], x)
        self._last = (state, prev)
        return outs

    def _select_by_src(self, outs: dict, src: jax.Array) -> jax.Array:
        """Each receiver keeps the copy carried by its *sender's* engine."""
        acc = outs[self._uniq[0]]
        for b in self._uniq[1:]:
            acc = jnp.where(self._masks[b][src], outs[b], acc)
        return acc

    def shift(self, x: jax.Array, k: int = 1) -> jax.Array:
        if k % self.n_nodes == 0:
            return x
        if not self.is_heterogeneous:
            return self._engines[self._uniq[0]].shift(x, k)
        outs = self._each(lambda e, v: e.shift(v, k), x)
        src = lax.rem(self.my_id() - k + 2 * self.n_nodes, self.n_nodes)
        return self._select_by_src(outs, src)

    def permute(self, x: jax.Array, dst: Sequence[int]) -> jax.Array:
        if not self.is_heterogeneous:
            return self._engines[self._uniq[0]].permute(x, dst)
        has_none = any(d is None for d in dst)
        if has_none and not self.can_permute_partial:
            raise ValueError(
                "partial permute (None destinations) unsupported by "
                f"engine map {self.backends}"
            )
        outs = self._each(lambda e, v: e.permute(v, dst), x)
        # receiver j's sender is inv[j]; non-destinations receive zeros
        # from every member engine, so any branch is correct for them.
        inv = [0] * self.n_nodes
        for s, d in enumerate(dst):
            if d is not None:
                inv[int(d)] = s
        src = jnp.asarray(inv, jnp.int32)[self.my_id()]
        return self._select_by_src(outs, src)

    # -- collectives: the put algorithms over the mixed edge transport --- #
    def all_gather(self, x: jax.Array) -> jax.Array:
        from repro.core import collectives

        return collectives.ring_all_gather(self, x)

    def reduce_scatter(self, x: jax.Array) -> jax.Array:
        from repro.core import collectives

        return collectives.ring_reduce_scatter(self, x)

    def all_reduce(self, x: jax.Array) -> jax.Array:
        from repro.core import collectives

        return collectives.ring_all_reduce(self, x)

    # all_to_all: inherited split-phase exchange over shift_nb.


def _make_single_engine(backend: str, axis: str, n_nodes: int) -> CommEngine:
    if backend == "xla":
        return XlaEngine(axis, n_nodes)
    if backend == "gascore":
        return GascoreEngine(axis, n_nodes)
    raise ValueError(f"unknown engine backend {backend!r}")


def parse_backend_spec(backend, n_nodes: int) -> Tuple[str, ...]:
    """Normalize a backend spec to one name per rank.

    Accepts a single name (``"xla"``), a comma-separated per-rank pattern
    (``"xla,gascore"`` — tiled around the ring when shorter than
    ``n_nodes``), or a sequence of names.
    """
    if isinstance(backend, str):
        names = [b.strip() for b in backend.split(",") if b.strip()]
    else:
        names = [str(b) for b in backend]
    if not names:
        raise ValueError("empty engine backend spec")
    if n_nodes % len(names):
        raise ValueError(
            f"backend pattern {names} (len {len(names)}) does not tile "
            f"{n_nodes} nodes"
        )
    return tuple(names[i % len(names)] for i in range(n_nodes))


def make_engine(backend, axis: str, n_nodes: int) -> CommEngine:
    """Build the engine (or heterogeneous :class:`EngineMap`) for a mesh axis.

    ``backend`` is a single engine name, a comma-separated per-rank pattern,
    or a sequence of per-rank names — ``make_engine("xla,gascore", ...)``
    gives alternating software/hardware nodes, the paper's mixed cluster.
    """
    ranks = parse_backend_spec(backend, n_nodes)
    uniq = set(ranks)
    if len(uniq) == 1:
        return _make_single_engine(ranks[0], axis, n_nodes)
    return EngineMap(axis, ranks)
