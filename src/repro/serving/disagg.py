"""Disaggregated prefill/decode serving over the GAS layer.

The cluster is one GASNet job over a ``node`` mesh axis
(``launch.mesh.serve_roles``): the first ``n_prefill`` ranks form the
prefill pool, the rest the decode pool, each pool optionally on its own
engine (``role_backends`` -> ``EngineMap`` — the paper's mixed
software/hardware cluster, serving-shaped).  Two planes:

- **Data plane** — a finished request's KV cache is flattened into one
  carrier block (:class:`~repro.serving.kv.KVLayout`), published in the
  prefill node's GASNet segment, and pushed into a staging slot of the
  decode node's segment with ``sched.plan_p2p``-planned segmented
  split-phase puts (:func:`~repro.serving.kv.push_block`).

  With ``paged=True`` the decode segments instead hold the **global
  paged KV pool** (:mod:`repro.serving.pool`): the prefill rank writes
  fixed-size token *pages* directly into the pool shard of the decode
  rank — one pred-gated put per page, landing at the exact page slots
  the (host-side, functional) allocator assigned, with NO dense staging
  copy in between.  Pages whose prompt-prefix chain is already resident
  on the target rank are not shipped at all: their puts trace with
  ``pred=False`` and the new request's page table simply maps the same
  physical pages (refcounted prefix sharing).
- **Control plane** — pure Active Messages: a ``kv_ready`` *request*
  (AMShort: request id, slot, origin) rides with the data; the decode
  node's handler records the slot in its inbox and returns an AMShort
  *reply* acknowledging installation, which resolves the prefill node's
  :class:`~repro.core.extended.AckHandle`; when decode finishes a request
  a ``req_done`` AM notifies the origin prefill rank (completion plane).
- **Tier plane** (``n_memory > 0``, paged only) — the paper's memory-node
  archetype: extra *memory* ranks export segment capacity but run no
  model compute, and the pool becomes a two-tier hierarchy.  Admission is
  lazy (only prompt pages materialise), so the pool oversubscribes; when
  a queued request cannot place, the SLO-aware scheduler
  (:mod:`repro.serving.scheduler`) preempts victims — pages swap OUT to a
  memory rank as one vectored put (``Node.put_nbv``: payloads + tier-slot
  offsets in one command block, ``repro.serving.tier.swap_out_pages``)
  and swap back IN at resume as one vectored get, both riding the same
  tick's SPMD transfer program; or, when the β model prices it cheaper,
  the victim recomputes (pages dropped, resume re-prefills and replays
  its generated tokens bit-identically).  Preempted requests resume
  bit-exactly: the restored pages are the carrier bytes that left.

Every tick the host launches the (jitted, perm-cached) SPMD transfer
program asynchronously, runs one continuous-batching decode step on every
decode server while the transfer is in flight, then consumes the
transfer's results — transfer/decode overlap in the split-phase style the
Extended API exists for.

All of this is single-process SPMD emulation (host devices as nodes),
exactly like the testing suites; the GAS programs are the same ones a
multi-host launch would run.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from repro.launch import mesh as mesh_lib
from repro.obs import export as obs_export
from repro.obs import health as health_lib
from repro.obs import trace as obs_trace
from repro.obs.metrics import Registry, counter_property
from repro.serving import kv as kv_lib


class DisaggCluster:
    """A role-based serving cluster: prefill pool + decode pool + AM
    control plane, all over one GAS context.

    ``prefill_backend`` / ``decode_backend`` name each pool's engine
    (mixing them yields an ``EngineMap``).  ``n_slots`` is the number of
    KV staging slots per decode node's segment (in paged mode: in-flight
    installs per rank — the data lands in pages); ``decode_batch`` the
    continuous-batching width of each decode server.

    ``paged=True`` replaces the dense staging slots with the global paged
    KV pool: each decode rank's segment is its pool shard
    (``pages_per_rank`` pages of ``page_tokens`` tokens), prefill ranks
    put pages straight into their allocator-assigned slots, and
    prompt-prefix-shared pages are mapped, not moved.

    Cluster statistics live on one typed
    :class:`~repro.obs.metrics.Registry` (``self.metrics``, shared with
    the admission scheduler and the memory tier): cumulative counts are
    Counters, point-in-time values are Gauges, and
    :meth:`reset_metrics` clears only the former.  Pass ``metrics`` to
    share a registry with a tracer so RMA byte accounting and cluster
    counters land in one place.
    """

    HEADER = 2  # carrier elems prepended to each block: first_token, pos

    # cumulative counters, registry-backed (explicit Counter kind); the
    # increment sites keep plain `self.x += 1` syntax via the proxy
    kv_transfers = counter_property("kv_transfers")
    kv_acked = counter_property("kv_acked")
    kv_pages_sent = counter_property("kv_pages_sent")
    kv_pages_shared = counter_property("kv_pages_shared")
    decoded_tokens = counter_property("decoded_tokens")
    dropped_am = counter_property("am_dropped")
    swap_out_bytes = counter_property("swap_out_bytes")
    swap_in_bytes = counter_property("swap_in_bytes")
    rank_failures = counter_property("rank_failures")
    recovered_recompute = counter_property("recovered_recompute")
    recovered_reroutes = counter_property("recovered_reroutes")
    elastic_joins = counter_property("elastic_joins")
    migrated_prefix_pages = counter_property("migrated_prefix_pages")

    def __init__(
        self,
        model: Any,
        ctx: Any,
        params: Any,
        *,
        n_prefill: int = 1,
        n_decode: int = 1,
        n_memory: int = 0,
        decode_batch: int = 4,
        cache_len: int = 64,
        n_slots: int = 2,
        prefill_backend: str = "xla",
        decode_backend: str = "xla",
        memory_backend: str = "xla",
        node_axis: str = "node",
        eos_id: int = -1,
        costs: Optional[Dict[str, Any]] = None,
        paged: bool = False,
        page_tokens: int = 8,
        pages_per_rank: Optional[int] = None,
        mem_slots_per_rank: Optional[int] = None,
        decode_step_us: float = 2000.0,
        prefill_us: float = 4000.0,
        tp: int = 1,
        tp_backend: Optional[str] = None,
        heartbeat_timeout: int = 3,
        tier_replicas: int = 1,
        replicate_all_swaps: bool = False,
        n_spare: int = 0,
        metrics: Optional[Registry] = None,
        flight_ticks: int = 64,
    ):
        import jax
        import jax.numpy as jnp

        from jax import shard_map

        from repro.core import am, gasnet, sched
        from repro.launch.serve import (
            PooledDecodeServer, Server, TPPooledDecodeServer,
        )
        from repro.runtime.ft import HeartbeatMonitor
        from repro.serving import pool as pool_lib
        from repro.serving import scheduler as sched_lib
        from repro.serving import tier as tier_lib

        if n_memory and not paged:
            raise ValueError("memory ranks require paged=True (page swap)")
        if n_spare and not paged:
            raise ValueError("spare ranks require paged=True (elastic join)")
        if tp > 1:
            if not paged:
                raise ValueError(
                    "tp > 1 requires paged=True (the TP group shards the "
                    "page pool by heads)"
                )
            if n_memory:
                raise ValueError(
                    "TP decode groups not yet composed with memory tiering"
                )

        self.jax, self.jnp = jax, jnp
        self.gasnet = gasnet
        self.shard_map = shard_map
        # the typed registry every counter/gauge below lives on — created
        # before any counter_property assignment runs
        self.metrics = metrics if metrics is not None else Registry()
        self.flight_ticks = flight_ticks
        self.flight_dumps: List[Dict[str, Any]] = []
        self.model, self.ctx, self.params = model, ctx, params
        self._rank_params: Dict[int, Any] = {}
        self.n_prefill, self.n_decode = n_prefill, n_decode
        self.n_memory = n_memory
        self.n_spare = n_spare
        self.n = n_prefill + n_decode + n_memory + n_spare
        self._memory_base = n_prefill + n_decode
        self.cache_len = cache_len
        self.n_slots = n_slots
        self.node_axis = node_axis
        self.max_done = decode_batch
        self.costs = costs
        self.paged = paged
        self.tp = tp
        self.tp_backend = tp_backend or decode_backend
        self.n_groups = n_decode // tp if tp else n_decode
        self._decode_batch = decode_batch
        self._eos_id = eos_id

        self.roles = mesh_lib.serve_roles(
            n_prefill, n_decode, n_memory, tp=tp, n_spare=n_spare
        )
        backends = mesh_lib.role_backends(
            self.roles, prefill=prefill_backend, decode=decode_backend,
            memory=memory_backend,
        )
        self._backends = backends
        # decode-group leader ranks, extensible: an elastic join appends a
        # promoted spare here, so every "decode rank of group g" lookup
        # stays a table read and survives membership changes
        self.group_leaders = [n_prefill + g * tp for g in range(self.n_groups)]
        self.mesh = mesh_lib.make_mesh((self.n,), (node_axis,))
        self.gas = gasnet.Context(
            self.mesh,
            node_axis=node_axis,
            backend=backends,
            am_capacity=self.max_done + 4,
            am_payload_width=1,
        )

        # ---- KV layout (static: shapes depend only on cache_len) --------
        if paged:
            # page-granular pool shards: one per decode rank's segment
            self.playout = pool_lib.PagedLayout.from_struct(
                model.kv_block_struct(ctx, prompt_len=4, cache_len=cache_len),
                cache_len=cache_len,
                page_tokens=page_tokens,
            )
            self.pages_per_rank = pages_per_rank or (
                (decode_batch + n_slots) * self.playout.n_pages
            )
            self.block_elems = self.playout.n_pages * self.playout.page_elems
            self.block_bytes = self.block_elems * 4
            # TP groups stripe every page BY HEADS across the group's
            # member segments: each member's pool partition holds the
            # shard-layout slice of all pages (tp=1: the full layout, an
            # identity stripe — one unified code path).
            self.shard_layout, self.shard_cols = self.playout.shard_heads(
                tp, model.cfg.n_kv_heads
            )
            self.seg_elems = self.pages_per_rank * self.shard_layout.page_elems
            # per-PAGE put plan: each page (shard slice) is its own
            # planned transfer
            self.plan = sched.plan_p2p(
                nbytes=self.shard_layout.page_bytes,
                engine=self.gas.make_engine(),
                costs=costs,
            )
            self.stores = [
                pool_lib.PagedKVStore(self.shard_layout, self.pages_per_rank)
                for _ in range(self.n_groups)
            ]
            # ---- tiered KV memory: memory-only ranks + preemption ------
            self.max_swap = self.playout.n_pages  # one request per tick
            # one request's pages per vectored swap/fetch transfer; built
            # even without memory ranks — the elastic-join prefix
            # migration rides the same vectored-get plane
            self.swap_plan = sched.plan_p2p(
                nbytes=self.max_swap * self.playout.page_bytes,
                engine=self.gas.make_engine(),
                costs=costs,
            )
            if n_memory:
                self.mem_slots = mem_slots_per_rank or (
                    2 * decode_batch * self.playout.n_pages
                )
                self.tier = tier_lib.MemoryTier(
                    n_memory, self.mem_slots, self.playout.page_elems,
                    replicas=max(1, min(tier_replicas, n_memory)),
                    registry=self.metrics,
                )
                self.seg_elems = max(
                    self.seg_elems, self.mem_slots * self.playout.page_elems
                )
            else:
                self.tier = None
            self.scheduler = sched_lib.AdmissionScheduler(
                page_bytes=self.playout.page_bytes, costs=costs,
                decode_step_us=decode_step_us, prefill_us=prefill_us,
                registry=self.metrics,
            )
            # live SLO monitor on the tick clock: tracked per submit,
            # ticked per cluster tick; its backpressure floor makes the
            # scheduler defer below-floor admissions while a deadline is
            # at risk.  Inert until a request carries finite deadlines.
            self.health = health_lib.HealthMonitor(registry=self.metrics)
            self.scheduler.attach_health(self.health)
        else:
            self.layout = kv_lib.KVLayout.from_struct(
                model.kv_block_struct(ctx, prompt_len=4, cache_len=cache_len)
            )
            self.block_elems = self.layout.total + self.HEADER
            self.block_bytes = self.block_elems * 4
            self.seg_elems = self.n_slots * self.block_elems
            self.plan = sched.plan_p2p(
                nbytes=self.block_bytes,
                engine=self.gas.make_engine(),
                costs=costs,
            )
            self.tier = None
            self.swap_plan = None
            self.scheduler = None
            self.health = None
            self.max_swap = 1

        # ---- AM control plane ------------------------------------------
        handlers = self.gas.handlers

        def kv_ack(state, payload, args):
            del payload
            out = dict(state)
            out["acks"] = state["acks"].at[args[1]].set(args[0] + 1)
            return out

        ack_id = handlers.register("kv_ack", kv_ack)

        def kv_ready(state, payload, args):
            rid, slot, origin = args[0], args[1], args[2]
            row = jnp.stack([jnp.ones((), jnp.int32), rid, origin])
            out = dict(state)
            out["inbox"] = state["inbox"].at[slot].set(row)
            return out, am.reply_short(ack_id, args=(rid, slot), like=payload)

        handlers.register("kv_ready", kv_ready, replies=True)

        def req_done(state, payload, args):
            del payload, args
            out = dict(state)
            out["done"] = state["done"] + 1
            return out

        handlers.register("req_done", req_done)

        # ---- device-side cluster state (host-managed between ticks) ----
        self.kvseg = np.zeros((self.n, self.seg_elems), np.float32)
        self.inbox = np.zeros((self.n, n_slots, 3), np.int32)
        self.acks = np.zeros((self.n, n_slots), np.int32)
        self.done = np.zeros((self.n, 1), np.int32)
        # live views of each group member's pool-partition mirror, re-bound
        # in place after every transfer consume (entry 0 = the leader's,
        # aliased as store.mem)
        self.shard_mems: List[List[Optional[np.ndarray]]] = [
            [None] * tp for _ in range(self.n_groups)
        ]
        if paged:
            self._alias_store_mem()

        # ---- pools ------------------------------------------------------
        # paged clusters decode THROUGH the page table — the same single
        # decode path (Model.decode_step_paged) as the colocated
        # PagedServer; the dense Server survives only as the oracle for
        # the unpaged (paged=False) handoff.
        if paged and tp > 1:
            devices = jax.devices()
            self.decode_servers = [
                TPPooledDecodeServer(
                    model, ctx, params, decode_batch, cache_len,
                    store=self.stores[g], shard_mems=self.shard_mems[g],
                    tp=tp, tp_backend=self.tp_backend,
                    tp_mesh=mesh_lib.make_mesh(
                        (tp,), ("tp",),
                        devices=devices[
                            self.decode_rank(g): self.decode_rank(g) + tp
                        ],
                    ),
                    costs=costs, eos_id=eos_id,
                    on_page_shortage=(
                        lambda rid, need, g=g:
                        self._decode_shortage(g, rid, need)
                    ),
                )
                for g in range(self.n_groups)
            ]
        elif paged:
            self.decode_servers = [
                PooledDecodeServer(
                    model, ctx, self._params_on(self.decode_rank(d)),
                    decode_batch, cache_len,
                    store=self.stores[d], eos_id=eos_id,
                    on_page_shortage=(
                        lambda rid, need, d=d:
                        self._decode_shortage(d, rid, need)
                    ),
                )
                for d in range(n_decode)
            ]
        else:
            self.decode_servers = [
                Server(model, ctx, params, decode_batch, cache_len,
                       eos_id=eos_id)
                for _ in range(n_decode)
            ]
        for d, srv in enumerate(self.decode_servers):
            srv.trace_rank = self.decode_rank(d)
        self._prefill_fn = jax.jit(
            lambda p, b: model.prefill(p, ctx, b, cache_len=cache_len)
        )

        # ---- host scheduler state --------------------------------------
        self.queue: List[Any] = []
        self.by_rid: Dict[int, Any] = {}
        self.finished: List[Any] = []
        # one in-flight push per prefill worker: (request, pool, slot, block)
        self.pending_push: List[Optional[Tuple]] = [None] * n_prefill
        self.staged: List[Dict[int, int]] = [
            dict() for _ in range(self.n_groups)
        ]
        self._done_queue: List[Tuple[int, int, int]] = []  # (g, rid+1, origin)
        self._finished_seen = [0] * self.n_groups
        self._rr_decode = 0
        self._transfer_fns: Dict[Tuple, Any] = {}
        self.kv_transfers = 0
        self.kv_acked = 0
        self.kv_pages_sent = 0
        self.kv_pages_shared = 0
        self.decoded_tokens = 0
        self.dropped_am = 0
        # ---- tiered-memory scheduler state -----------------------------
        # rid -> preemption snapshot (mode, decode pos, last token, pages)
        self._preempted: Dict[int, Dict[str, Any]] = {}
        # staged swap-outs: (rid, d, src_offsets, legs) — legs is a tuple
        # of (memory rank, dst_offsets), one vectored put per replica leg
        self._swap_jobs: List[Tuple] = []
        # staged swap-ins: (rid, d, remote_offsets, local_offsets,
        # src_rank) — src is a memory rank for tier resumes, a donor
        # decode leader for elastic-join prefix migration (rid == -1)
        self._fetch_jobs: List[Tuple] = []
        self._inflight_swap: Optional[Tuple] = None
        self._inflight_fetch: Optional[Tuple] = None
        # rid -> decode pool index whose shard holds the restored pages,
        # waiting for a free decode row
        self._installable: Dict[int, int] = {}
        self.swap_out_bytes = 0
        self.swap_in_bytes = 0
        # ---- fault tolerance + elasticity ------------------------------
        # membership is tick-clocked: every live rank "beats" once per
        # tick (in a multi-host launch the beat would be an AM), and the
        # monitor declares a rank dead after ``heartbeat_timeout`` missed
        # ticks — detection within K ticks by construction.
        self._tick_no = 0
        self.monitor = HeartbeatMonitor(
            list(range(self.n)),
            timeout_s=float(heartbeat_timeout),
            clock=lambda: float(self._tick_no),
        )
        self.killed: set = set()       # fault injection: ranks to stop beating
        self.dead_ranks: set = set()   # monitor-declared failures
        self.dead_groups: set = set()  # decode groups with a dead member
        self.fault_hook = None         # callable(cluster, phase, tick)
        self.beat_filter = None        # callable(rank, tick) -> bool
        self.replicate_all_swaps = replicate_all_swaps
        self.max_replicas = self.tier.replicas if self.tier is not None else 1
        self.rank_failures = 0
        self.recovered_recompute = 0
        self.recovered_reroutes = 0
        self.elastic_joins = 0
        self.migrated_prefix_pages = 0
        # in-flight prefix-index migration to a freshly joined group:
        # {"donor": g, "n": pages} until its vectored get lands
        self._pending_migration: Optional[Dict[str, int]] = None

    # ------------------------------------------------------------------ #
    # role views
    # ------------------------------------------------------------------ #
    def decode_rank(self, d: int) -> int:
        """Rank of decode GROUP ``d``'s leader (= its only member at
        tp=1): the rank whose pool partition backs the group's store and
        which receives the group's control-plane AMs."""
        return self.group_leaders[d]

    def member_rank(self, g: int, s: int) -> int:
        """Rank of member ``s`` of decode group ``g`` (its head shard)."""
        return self.group_leaders[g] + s

    def memory_rank(self, m: int) -> int:
        return self._memory_base + m

    def _params_on(self, rank: int) -> Any:
        """The weights on ``rank``'s own device: each rank's model compute
        runs on its own chip (inputs from the host follow the weights)."""
        if rank not in self._rank_params:
            self._rank_params[rank] = self.jax.device_put(
                self.params, self.mesh.devices.flat[rank]
            )
        return self._rank_params[rank]

    def _group_down(self, g: int) -> bool:
        """True when any member rank of decode group ``g`` is killed or
        declared dead — a TP group fails as a unit."""
        if g in self.dead_groups:
            return True
        return any(
            self.member_rank(g, s) in self.killed
            or self.member_rank(g, s) in self.dead_ranks
            for s in range(self.tp)
        )

    def _alias_store_mem(self) -> None:
        """Point each decode store's physical page array at its group
        leader's partition of the (freshly consumed) pool segment — the
        host mirror of the PGAS shard — and re-bind every group member's
        shard mirror (``shard_mems``) in place.  Pages arrive over the
        wire (admission puts, swap-in gets) AND from the paged decode
        step, which writes each tick's token page in place; decode writes
        made while a transfer was in flight are replayed onto the fresh
        mirror by :meth:`_apply_decode_writes`."""
        pool_elems = self.pages_per_rank * self.shard_layout.page_elems
        for g, store in enumerate(self.stores):
            views = [
                self.kvseg[self.member_rank(g, s)][:pool_elems].reshape(
                    self.pages_per_rank, self.shard_layout.page_elems
                )
                for s in range(self.tp)
            ]
            store.mem = views[0]
            for s in range(self.tp):
                self.shard_mems[g][s] = views[s]

    # ------------------------------------------------------------------ #
    # request intake
    # ------------------------------------------------------------------ #
    def submit(self, req: Any) -> None:
        req.t_enqueue = time.monotonic()
        tr = obs_trace.active()
        if tr.enabled:
            tr.instant(
                "req_submit", cat="req", rid=req.rid,
                prompt_len=len(req.prompt),
            )
        self.queue.append(req)
        self.by_rid[req.rid] = req
        if self.scheduler is not None:
            from repro.serving.scheduler import SLO

            self.scheduler.submit(
                req.rid, getattr(req, "slo", None) or SLO(),
                prompt_len=len(req.prompt), now=req.t_enqueue,
            )
            if self.health is not None:
                self.health.track(
                    req.rid, getattr(req, "slo", None) or SLO(),
                    req.t_enqueue,
                )

    # ------------------------------------------------------------------ #
    # SPMD transfer program (data plane + control plane, one launch)
    # ------------------------------------------------------------------ #
    def _transfer_fn(
        self,
        perm: Tuple[int, ...],
        perm_swap: Optional[Tuple[Tuple[int, ...], ...]] = None,
        perm_fetch: Optional[Tuple[int, ...]] = None,
    ) -> Any:
        key = (perm, perm_swap, perm_fetch)
        cached = self._transfer_fns.get(key)
        if cached is not None:
            return cached
        jax = self.jax
        gasnet = self.gasnet
        from jax.sharding import PartitionSpec as P

        from repro.serving import pool as pool_lib
        from repro.serving import tier as tier_lib

        spec = P(self.node_axis)
        block = self.block_elems

        def data_plane_dense(node, kvseg, outflat, meta):
            has = meta[0, 0] > 0
            slot = meta[0, 2]
            handles, _ = kv_lib.push_block(
                node,
                kvseg,
                outflat[0],
                to=gasnet.Perm(perm),
                base_index=slot * block,
                pred=has,
                plan=self.plan,
            )
            return handles

        def data_plane_paged(node, kvseg, outflat, meta, page_meta):
            # one pred-gated put per page PER HEAD SHARD, each shard's
            # slice landing at the allocator's slot of its group member's
            # segment (page_meta[j] = flat pool offset, send flag — the
            # same offset on every member: the partitions are congruent);
            # prefix-shared pages trace with pred=False and ship nothing.
            # ``perm`` is a tuple of per-shard permutations (length tp).
            has = meta[0, 0] > 0
            handles = []
            for s, pm in enumerate(perm):
                for j in range(self.playout.n_pages):
                    hs, _ = kv_lib.push_block(
                        node,
                        kvseg,
                        outflat[0, s, j],
                        to=gasnet.Perm(pm),
                        base_index=page_meta[0, j, 0],
                        pred=has & (page_meta[0, j, 1] > 0),
                        plan=self.plan,
                    )
                    handles.extend(hs)
            return handles

        def body(kvseg, inbox, acks, done, outflat, meta, page_meta,
                 done_meta, swap_meta, fetch_meta):
            node = self.gas.make_node()
            has = meta[0, 0] > 0
            rid, slot, dst = meta[0, 1], meta[0, 2], meta[0, 3]
            # data plane: planned segmented split-phase puts
            if self.paged:
                handles = data_plane_paged(node, kvseg, outflat, meta, page_meta)
            else:
                handles = data_plane_dense(node, kvseg, outflat, meta)
            # tier plane: swap-out rides the vectored put (victim pages +
            # tier slot offsets in one command block), swap-in the
            # vectored get — both split-phase, in flight alongside the
            # admission puts and the AM control plane.  Replication fans
            # the SAME victim pages once per placement leg: one vectored
            # put per replica, each to its own memory rank (perm_swap is
            # a tuple of per-leg permutations; unused legs gate off via
            # zero flags).
            swap_handles = []
            fetch_handles = None
            if perm_swap is not None:
                for li, pm in enumerate(perm_swap):
                    hs, _ = tier_lib.swap_out_pages(
                        node, kvseg,
                        swap_meta[0, li, :, 0], swap_meta[0, li, :, 1],
                        to=gasnet.Perm(pm),
                        page_elems=self.playout.page_elems,
                        flags=swap_meta[0, li, :, 2],
                        plan=self.swap_plan,
                    )
                    swap_handles.extend(hs)
            if perm_fetch is not None:
                # in-step page prefetch: the pool's split-phase vectored
                # fetch (plan-batched get_nbv) is issued HERE and drained
                # only after the puts and control plane below — and the
                # host overlaps one whole paged decode step before
                # consuming this program, so the wire hides behind the
                # decode compute.
                fetch_handles, _ = pool_lib.fetch_pages(
                    node,
                    kvseg,
                    fetch_meta[0, :, 0],
                    frm=gasnet.Perm(perm_fetch),
                    page_elems=self.playout.page_elems,
                    plan=self.swap_plan,
                    pred=fetch_meta[0, :, 2].max() > 0,
                )
            # control plane rides while the puts are in flight
            ackh = node.am_call(
                dst,
                "kv_ready",
                args=(rid, slot, node.my_id),
                pred=has,
                ack=lambda st: st["acks"],
            )
            for j in range(self.max_done):
                node.am_short(
                    done_meta[0, j, 1],
                    "req_done",
                    args=(done_meta[0, j, 0],),
                    pred=done_meta[0, j, 0] > 0,
                )
            kvseg = kv_lib.sync_push(node, kvseg, handles)
            for h in swap_handles:
                kvseg = node.sync(h)
            if fetch_handles is not None:
                fetched = pool_lib.sync_fetch(node, fetch_handles)
                kvseg = tier_lib.install_pages(
                    node, kvseg, fetched,
                    fetch_meta[0, :, 1], fetch_meta[0, :, 2],
                )
            state = {"inbox": inbox[0], "acks": acks[0], "done": done[0]}
            state = node.am_flush(state)
            acked = node.sync(ackh)
            return (
                kvseg,
                state["inbox"][None],
                acked[None],
                state["done"][None],
                node.dropped[None],
            )

        fn = jax.jit(
            self.shard_map(
                body,
                mesh=self.mesh,
                in_specs=(spec,) * 10,
                out_specs=(spec,) * 5,
                check_vma=False,
            )
        )
        self._transfer_fns[key] = fn
        return fn

    # ------------------------------------------------------------------ #
    # host scheduler
    # ------------------------------------------------------------------ #
    def _pick_target(
        self, taken: set, prompt: Optional[Any] = None
    ) -> Optional[Tuple[int, int]]:
        """(decode pool index, staging slot) with capacity, round-robin.

        Paged mode adds two rules: the target rank must hold enough free
        pool pages for a worst-case (unshared) admission, and ranks are
        tried in order of *prefix affinity* — the rank whose pool already
        holds the longest leading run of the prompt's pages wins, so the
        shared pages are mapped instead of moved."""
        order = [
            d
            for d in (
                (self._rr_decode + i) % self.n_groups
                for i in range(self.n_groups)
            )
            if not self._group_down(d)
        ]
        if self.paged and prompt is not None:
            matches = {d: self.stores[d].prefix_match(prompt) for d in order}
            best = max(matches.values())
            if best > 0:
                # hard affinity: only ranks holding the longest resident
                # prefix qualify — admitting elsewhere would re-ship pages
                # that already exist.  If they are busy this tick the
                # request waits one tick (head-of-line, bounded: slots and
                # pages free as decodes finish).
                order = [d for d in order if matches[d] == best]
        for d in order:
            if d in taken:
                continue
            if self.paged:
                need = (
                    self.playout.pages_for(len(prompt))
                    if prompt is not None
                    else self.playout.n_pages
                )
                if self.stores[d].n_free < need:
                    continue
            for slot in range(self.n_slots):
                if slot not in self.staged[d]:
                    self._rr_decode = (d + 1) % self.n_groups
                    return d, slot
        return None

    def _admission_queue(self) -> List[Any]:
        """The submit queue in scheduler order (priority-major, EDF within
        a priority) when paged; FIFO otherwise."""
        if not (self.paged and self.scheduler is not None):
            return list(self.queue)
        pos = {
            rid: i for i, rid in enumerate(self.scheduler.admission_order())
        }
        return sorted(
            self.queue, key=lambda r: pos.get(r.rid, len(pos) + r.rid)
        )

    def _run_prefills(self) -> None:
        """Assign queued requests to idle prefill workers (host compute)."""
        taken = {push[1] for push in self.pending_push if push is not None}
        order = self._admission_queue()
        for p in range(self.n_prefill):
            if p in self.killed or p in self.dead_ranks:
                continue  # dead prefill workers take no new requests
            if self.pending_push[p] is not None or not order:
                continue
            req = order[0]
            target = self._pick_target(taken, prompt=req.prompt)
            if target is None:
                # oversubscribed: try to preempt for the head-of-order
                # request (tiered clusters only)
                if self.paged and self.tier is not None:
                    self._try_preempt_for(req)
                return
            d, slot = target
            order.pop(0)
            self.queue.remove(req)
            jnp = self.jnp
            tr = obs_trace.active()
            with tr.span(
                "prefill", cat="req", rank=p, rid=req.rid,
                prompt_len=len(req.prompt), group=d,
            ):
                toks = jnp.asarray(req.prompt, jnp.int32)[None]
                logits, caches_one = self._prefill_fn(
                    self._params_on(p), {"inputs": toks}
                )
                tok = int(np.argmax(np.asarray(logits)[0]))
            if not req.out:  # a recompute-resume already holds its tokens
                req.out.append(tok)
                req.t_first = time.monotonic()
                if tr.enabled:
                    tr.instant(
                        "req_first_token", cat="req", rank=p, rid=req.rid
                    )
                if self.health is not None:
                    self.health.first_token(req.rid, req.t_first)
            if self.paged:
                # the pool's allocator assigns the pages NOW (host control
                # plane); the page payloads go one-sided into those exact
                # slots of the decode rank's pool shard — no staging copy,
                # and prefix-shared pages ship nothing at all.  Lazy: only
                # prompt pages materialise, so the pool oversubscribes.
                pages = np.asarray(self.playout.flatten(caches_one))
                # pre-stripe each page by heads for the group members:
                # (tp, n_pages, shard_page_elems); tp=1 is the identity
                # stripe (one unified path)
                shards = pages[:, self.shard_cols].transpose(1, 0, 2)
                plan = self.stores[d].plan_admit(req.prompt, lazy=True)
                self.stores[d].commit(req.rid, plan)
                self.pending_push[p] = (req, d, slot, shards, plan)
            else:
                header = np.asarray([tok, len(req.prompt)], np.int32).view(np.float32)
                flat = np.concatenate(
                    [header, np.asarray(self.layout.flatten(caches_one))]
                )
                self.pending_push[p] = (req, d, slot, flat, None)
            self.staged[d][slot] = req.rid
            taken.add(d)

    # ------------------------------------------------------------------ #
    # tiered memory: preemption, swap staging, resume
    # ------------------------------------------------------------------ #
    def _freeable(self, d: int, rid: int) -> int:
        return self.stores[d].freeable(rid)

    def _try_preempt_for(self, req: Any) -> None:
        """Head-of-order request found no rank with pages: preempt victims
        on the rank that can reclaim enough.  Strictly-lower-priority
        victims always qualify; equal-priority victims only once the
        beneficiary's TTFT deadline has expired (SLO pressure)."""
        from repro.serving.scheduler import SLO

        if self._swap_jobs or self._inflight_swap is not None:
            return  # one staged swap-out at a time
        need = self.playout.pages_for(len(req.prompt))
        slo = getattr(req, "slo", None) or SLO()
        expired = time.monotonic() > req.t_enqueue + slo.ttft_deadline_s
        for d in range(self.n_groups):
            if self._group_down(d):
                continue
            shortage = need - self.stores[d].n_free
            if shortage <= 0:
                continue  # pages are not this rank's blocker (slots are)
            if all(s in self.staged[d] for s in range(self.n_slots)):
                continue  # no staging slot: freeing pages would not help
            running = [
                r.rid for r in self.decode_servers[d].active if r is not None
            ]
            victims = self.scheduler.pick_victims(
                running,
                shortage,
                lambda rid, d=d: self._freeable(d, rid),
                beneficiary=req.rid,
                strict=not expired,
            )
            if victims:
                for rid in victims:
                    self._preempt(d, rid)
                return

    def _preempt(self, d: int, rid: int) -> None:
        """Evict one running request from decode rank ``d``: swap its
        pages to a memory rank (vectored-put job staged for this tick's
        transfer) or drop them for recompute-replay, per the β cost
        model."""
        from repro.serving import pool as pool_lib
        from repro.serving import tier as tier_lib

        server = self.decode_servers[d]
        store = self.stores[d]
        i = next(
            ix for ix, r in enumerate(server.active)
            if r is not None and r.rid == rid
        )
        req = server.active[i]
        pos = int(server.positions[i])
        last = int(server.last_token[i, 0])
        n_mat = self.playout.pages_for(pos)
        self.scheduler.entry(rid).generated = max(0, len(req.out) - 1)
        mode, swap_us, recompute_us = self.scheduler.choose_mode(rid, n_mat)
        tr = obs_trace.active()
        if tr.enabled:
            tr.instant(
                "req_preempt", cat="req", rank=self.decode_rank(d),
                rid=rid, mode=mode, n_pages=n_mat,
                swap_est_us=round(swap_us, 1),
                recompute_est_us=round(recompute_us, 1),
            )
        hold = None
        if mode == "swap":
            # replication policy: hot (prefix-shared) pages get every
            # tier replica — losing them would take several requests'
            # prefixes down at once; cold private pages default to one
            # leg unless the cluster opts everything in.
            want = 1
            if self.tier.replicas > 1 and (
                self.replicate_all_swaps or store.shared_page_count(rid) > 0
            ):
                want = self.tier.replicas
            try:
                store.materialize_through(rid, n_mat)
                hold = self.tier.plan_swap_out(
                    rid, list(range(n_mat)), replicas=want
                )
            except (pool_lib.OutOfPagesError, tier_lib.OutOfSlotsError):
                mode = "recompute"  # no room to stage: drop and replay
        if mode == "swap":
            # the pool shard IS the victim's current state: the paged
            # decode step writes every generated token's page in place,
            # and prompt pages are written once at admission (prefix
            # sharers included) — so unlike the old dense decode rows
            # there is nothing to stage; the swap-out job just ships the
            # victim's resident pages as they sit in the mirror, fanned
            # once per placement leg (ONE put_nbv per replica).
            table = store.page_table(rid)
            src = [table[lp] * self.playout.page_elems for lp in range(n_mat)]
            legs = tuple(
                (
                    self.memory_rank(pl.rank),
                    [self.tier.slot_offset(pl.rank, s) for s in pl.slots],
                )
                for pl in hold.placements
            )
            self._swap_jobs.append((rid, d, src, legs))
            self.swap_out_bytes += (
                n_mat * self.playout.page_bytes * len(legs)
            )
        else:
            store.evict_request(rid)
            self.queue.append(req)  # resume = re-prefill + replay
        replay = list(server.replaying.get(i, []))
        server.evict_row(i)
        self._preempted[rid] = {
            "mode": mode,
            "position": pos,
            "last_token": last,
            "n_mat": n_mat,
            "swapped": False,
            # a victim caught mid-replay resumes with its replay tail
            "replay": replay,
        }
        self.scheduler.on_preempted(rid, mode)

    def _decode_shortage(self, d: int, rid: int, need: int) -> bool:
        """A decode row's lazy page growth found rank ``d``'s pool shard
        dry mid-tick (tiered clusters oversubscribe): preempt victims for
        the growing row.  Returns False when no pages freed up *this
        tick* — a swap-mode victim's pages are released only once its
        vectored put lands on the memory rank — in which case the row
        stalls one tick and retries (see PooledDecodeServer.step)."""
        if self.scheduler is None:
            return False
        store = self.stores[d]
        # pages are already on their way: a staged/in-flight swap-out from
        # this rank frees its victim's pages when the vectored put lands —
        # stall instead of preempting MORE (else two residents ping-pong
        # through swap/resume without ever decoding)
        if any(job[1] == d for job in self._swap_jobs) or (
            self._inflight_swap is not None and self._inflight_swap[1] == d
        ):
            return False
        running = [
            r.rid for r in self.decode_servers[d].active
            if r is not None and r.rid != rid
        ]
        victims = self.scheduler.pick_victims(
            running, need - store.n_free,
            lambda v, d=d: self._freeable(d, v),
            beneficiary=rid, strict=False,
        )
        # no eligible victim and no landing pending: the growing row
        # preempts itself so its pages can serve whoever CAN progress
        for v in (victims or [rid]):
            self._preempt(d, v)
        return store.n_free >= need

    def _apply_decode_writes(self) -> None:
        """Replay this tick's decode-written pages onto the pool mirror.

        The decode step overlaps an in-flight transfer program whose
        consumed result REPLACES the whole segment the stores alias, so
        page writes made during the overlap must land again on the fresh
        mirror.  Transfer targets are disjoint from decode write pages by
        construction: admission puts and swap-in installs land only in
        freshly allocated (hence non-free, non-writable) pages, and
        swap-out destinations live on memory ranks."""
        if not self.paged:
            return
        for g, server in enumerate(self.decode_servers):
            if self._group_down(g):
                continue
            for pp, row in server.drain_dirty().items():
                if self.tp > 1:
                    # stacked (tp, shard_elems) rows: one slice per member
                    for s in range(self.tp):
                        self.shard_mems[g][s][pp] = row[s]
                else:
                    self.stores[g].mem[pp] = row

    def _run_resumes(self) -> None:
        """Stage swap-ins: a preempted-by-swap request whose pages sit in
        the tier resumes onto the decode rank with room — one vectored-get
        job per tick; the fetched pages install into a decode row once the
        transfer lands."""
        if not (self.paged and self.tier is not None):
            return
        if self._fetch_jobs or self._inflight_fetch is not None:
            return
        for rid in self.scheduler.admission_order():
            snap = self._preempted.get(rid)
            if (
                snap is None
                or snap["mode"] != "swap"
                or not snap["swapped"]
                or snap.get("staged")
                or rid in self._installable
            ):
                continue
            hold = self.tier.holdings[rid]
            # quorum restore: read from the first placement leg whose
            # memory rank is still alive — with a replica surviving, a
            # dead primary is invisible to the resume path
            pl = self.tier.restore_placement(rid)
            # growth headroom: when the resume position opens a FRESH page
            # (position on a page boundary), the first decode tick after
            # install needs one page beyond the restored set — resuming
            # without it would bounce straight back out
            need = len(hold.logical)
            if snap["position"] % self.playout.page_tokens == 0:
                need += 1
            best = None
            for d in range(self.n_groups):
                if self._group_down(d):
                    continue
                if self.stores[d].n_free >= need:
                    best = d
                    break
            if best is None:
                continue
            phys = self.stores[best].admit_resume(rid, hold.logical)
            remote = [self.tier.slot_offset(pl.rank, s) for s in pl.slots]
            local = [pp * self.playout.page_elems for pp in phys]
            self._fetch_jobs.append(
                (rid, best, remote, local, self.memory_rank(pl.rank))
            )
            snap["staged"] = True
            return

    def _install_resumed(self) -> None:
        """Bind restored requests to free decode rows: the swapped pages
        landed back in the pool shard at their new table slots, so the
        row resumes decoding through the page table exactly at the
        preempted position (bit-identical continuation)."""
        for rid, d in list(self._installable.items()):
            server = self.decode_servers[d]
            snap = self._preempted[rid]
            req = self.by_rid[rid]
            ok = server.admit_paged(
                req,
                first_token=snap["last_token"],
                position=snap["position"],
            )
            if not ok:
                continue  # no free row yet; pages stay resident
            if snap.get("replay"):
                row = next(
                    ix for ix, r in enumerate(server.active)
                    if r is not None and r.rid == rid
                )
                server.start_replay(row, snap["replay"])
            # a memory-rank failure may have scrubbed the holding after
            # the pages landed (they are already safe in the pool shard)
            if rid in self.tier.holdings:
                self.tier.release(rid)
            for s in self.stores:
                s.note_swap_in(rid)
            del self._installable[rid]
            del self._preempted[rid]
            self.scheduler.on_admitted(rid, time.monotonic())
            tr = obs_trace.active()
            if tr.enabled:
                tr.instant(
                    "req_resume", cat="req", rank=self.decode_rank(d),
                    rid=rid, position=snap["position"],
                )

    def _launch_transfer(self) -> Optional[Tuple[Any, ...]]:
        """Build this tick's transfer inputs and dispatch the SPMD program
        (asynchronously — the caller overlaps decode before consuming)."""
        pushes = [
            (p, push)
            for p, push in enumerate(self.pending_push)
            if push is not None
        ]
        if (
            not pushes
            and not self._done_queue
            and not self._swap_jobs
            and not self._fetch_jobs
        ):
            return None
        if self.paged:
            # one handoff permutation per head shard: prefill rank p's
            # shard-s slice goes to member s of its target group (at tp=1
            # a 1-tuple of the classic leader permutation)
            perm = tuple(
                kv_lib.handoff_permutation(
                    self.n,
                    {p: self.member_rank(d, s) for p, (_, d, _, _, _) in pushes},
                )
                for s in range(self.tp)
            )
        else:
            edges = {p: self.decode_rank(d) for p, (_, d, _, _, _) in pushes}
            perm = kv_lib.handoff_permutation(self.n, edges)
        # tier plane: at most one swap-out and one swap-in job per tick,
        # each its own completed bijection (decode rank -> memory rank);
        # a replicated swap-out fans one bijection per placement leg
        perm_swap = perm_fetch = None
        R = getattr(self, "max_replicas", 1)
        swap_meta = np.zeros((self.n, R, self.max_swap, 3), np.int32)
        fetch_meta = np.zeros((self.n, self.max_swap, 3), np.int32)
        if self.paged:
            if self._swap_jobs:
                job = self._swap_jobs.pop(0)
                _, d, src, legs = job
                rank = self.decode_rank(d)
                perms = []
                for li, (mrank, dst) in enumerate(legs):
                    for j, (s, t) in enumerate(zip(src, dst)):
                        swap_meta[rank, li, j] = (s, t, 1)
                    perms.append(
                        kv_lib.handoff_permutation(self.n, {rank: mrank})
                    )
                # pad unused legs with the identity permutation (their
                # flags are zero, so nothing ships) — the leg count stays
                # static across ticks and the jit cache stays small
                while len(perms) < R:
                    perms.append(kv_lib.handoff_permutation(self.n, {}))
                perm_swap = tuple(perms)
                self._inflight_swap = job
            if self._fetch_jobs:
                job = self._fetch_jobs.pop(0)
                _, d, remote, local, mrank = job
                rank = self.decode_rank(d)
                for j, (s, t) in enumerate(zip(remote, local)):
                    fetch_meta[rank, j] = (s, t, 1)
                perm_fetch = kv_lib.handoff_permutation(self.n, {rank: mrank})
                self._inflight_fetch = job
        if self.paged:
            outflat = np.zeros(
                (self.n, self.tp, self.playout.n_pages,
                 self.shard_layout.page_elems),
                np.float32,
            )
            page_meta = np.zeros((self.n, self.playout.n_pages, 2), np.int32)
        else:
            outflat = np.zeros((self.n, self.block_elems), np.float32)
            page_meta = np.zeros((self.n, 1, 2), np.int32)
        meta = np.zeros((self.n, 4), np.int32)
        for p, (req, d, slot, flat, aplan) in pushes:
            outflat[p] = flat
            meta[p] = (1, req.rid, slot, self.decode_rank(d))
            if self.paged:
                for j, (page_id, fresh) in enumerate(zip(aplan.table, aplan.fresh)):
                    # unmaterialised slots (lazy tail) park at offset 0,
                    # gated off like prefix-shared pages
                    page_meta[p, j] = (
                        max(page_id, 0) * self.shard_layout.page_elems,
                        1 if fresh else 0,
                    )
            if not getattr(req, "_push_counted", False):
                req._push_counted = True
                self.kv_transfers += 1
                if self.paged:
                    n_fresh = sum(aplan.fresh)
                    self.kv_pages_sent += n_fresh
                    self.kv_pages_shared += sum(
                        1 for pid, f in zip(aplan.table, aplan.fresh)
                        if pid >= 0 and not f
                    )
        done_meta = np.zeros((self.n, self.max_done, 2), np.int32)
        per_rank_counts = [0] * self.n
        leftover: List[Tuple[int, int, int]] = []
        for d, rid_plus1, origin in self._done_queue:
            rank = self.decode_rank(d)
            j = per_rank_counts[rank]
            if j < self.max_done:
                done_meta[rank, j] = (rid_plus1, origin)
                per_rank_counts[rank] = j + 1
            else:
                leftover.append((d, rid_plus1, origin))
        self._done_queue = leftover
        tr = obs_trace.active()
        if tr.enabled:
            # split-phase handoff span: initiation here, ended when the
            # consume lands — the KV-handoff window a decode step overlaps
            self._transfer_span = tr.begin_async(
                "kv_handoff", cat="transfer", pushes=len(pushes),
                done_reports=int(sum(per_rank_counts)),
                swap=self._inflight_swap is not None,
                fetch=self._inflight_fetch is not None,
                est_us=round(self.plan.est_us, 1),
            )
        fn = self._transfer_fn(perm, perm_swap, perm_fetch)
        return fn(
            self.kvseg,
            self.inbox,
            self.acks,
            self.done,
            outflat,
            meta,
            page_meta,
            done_meta,
            swap_meta,
            fetch_meta,
        )

    def _decode_step(self) -> None:
        """One continuous-batching tick on every decode server; collect
        newly finished requests as completion reports for the next
        transfer launch."""
        for d, server in enumerate(self.decode_servers):
            if self._group_down(d):
                continue  # a dead rank computes nothing from the kill on
            self.decoded_tokens += server.step()
            fresh = server.finished[self._finished_seen[d] :]
            self._finished_seen[d] = len(server.finished)
            for req in fresh:
                self.finished.append(req)
                if self.paged:
                    # drop the request's page references; prefix pages
                    # shared with live requests stay resident
                    self.stores[d].release(req.rid)
                    if self.scheduler is not None:
                        self.scheduler.on_done(req.rid)
                    if self.health is not None:
                        self.health.retire(req.rid)
                origin = getattr(req, "origin_rank", 0)
                self._done_queue.append((d, req.rid + 1, origin))

    def _consume_transfer(self, results: Tuple[Any, ...]) -> None:
        # np.array (not asarray): host copies must stay writable — the
        # scheduler clears inbox flags after installs
        kvseg, inbox, acks, done, dropped = (np.array(r) for r in results)
        sp = getattr(self, "_transfer_span", None)
        if sp is not None:
            self._transfer_span = None
            obs_trace.active().end_async(sp)
        self.kvseg, self.inbox, self.acks, self.done = kvseg, inbox, acks, done
        # death emulation: the consumed result replaces the whole segment
        # array, so re-poison every dead rank's mirror — any recovery path
        # that erroneously reads a "dead" rank's bytes breaks token parity
        # instead of silently passing
        for r in self.killed | self.dead_ranks:
            self.kvseg[r, :] = np.nan
        if self.paged:
            self._alias_store_mem()  # fresh host mirror of the pool shards
        self.dropped_am += int(dropped.sum())
        # tier plane completions: a landed swap-out releases the victim's
        # pool pages (never before the bytes are safe in the memory rank);
        # a landed swap-in becomes installable into a decode row.
        if self._inflight_swap is not None:
            rid, d, src, legs = self._inflight_swap
            if self._group_down(d):
                # the source rank died mid-put: the tier bytes are not
                # trustworthy — requeue; detection converts to recompute
                self._swap_jobs.insert(0, self._inflight_swap)
                self._inflight_swap = None
            else:
                self.stores[d].note_swap_out(
                    rid, len(src), replicas=len(legs) - 1
                )
                self.stores[d].evict_request(rid)
                self._preempted[rid]["swapped"] = True
                self._inflight_swap = None
        if self._inflight_fetch is not None:
            rid, d, remote, _, src_rank = self._inflight_fetch
            if (
                src_rank in self.killed
                or src_rank in self.dead_ranks
                or (rid >= 0 and self._group_down(d))
            ):
                # source or target died mid-get: the fetched bytes are
                # poison — requeue; detection re-stages or recomputes
                self._fetch_jobs.insert(0, self._inflight_fetch)
                self._inflight_fetch = None
            elif rid < 0:
                # elastic-join prefix migration landed: the joined group's
                # adopted pages now hold the donor's prefix bytes — unpin
                # the donor side and count the moved pages
                mig = self._pending_migration or {}
                donor = mig.get("donor")
                if donor is not None:
                    self.stores[donor].unpin_pages()
                self.migrated_prefix_pages += len(remote)
                self._pending_migration = None
                self.swap_in_bytes += len(remote) * self.playout.page_bytes
                self._inflight_fetch = None
            else:
                self._installable[rid] = d
                self.swap_in_bytes += len(remote) * self.playout.page_bytes
                self._inflight_fetch = None
        # prefill side: retire acknowledged pushes — but NEVER on the word
        # of a dead group: in the emulation the killed rank's program
        # still ran, so its acks must be voided host-side (on real
        # hardware they would simply never arrive)
        for p, push in enumerate(self.pending_push):
            if push is None:
                continue
            req, d, slot, _, _ = push
            if self._group_down(d):
                continue
            if int(self.acks[p, slot]) == req.rid + 1:
                self.kv_acked += 1
                req.origin_rank = p
                self.pending_push[p] = None
        # decode side: install staged blocks into servers with free rows
        for d, server in enumerate(self.decode_servers):
            if self._group_down(d):
                continue
            rank = self.decode_rank(d)
            for slot in range(self.n_slots):
                occupied = int(self.inbox[rank, slot, 0])
                rid = int(self.inbox[rank, slot, 1])
                if not occupied:
                    continue
                req = self.by_rid.get(int(rid))
                if req is None or self.staged[d].get(slot) != int(rid):
                    continue
                if self._install(server, rank, slot, req):
                    self.inbox[rank, slot, 0] = 0
                    del self.staged[d][slot]

    def _install(self, server, rank: int, slot: int, req) -> bool:
        if self.paged:
            # bind the decode row straight to the page table: the pool
            # shard is the KV source of truth and every decode tick runs
            # THROUGH it (Model.decode_step_paged) — no dense row is ever
            # gathered in the cluster hot path.
            ok = server.admit_paged(
                req, first_token=req.out[0], position=len(req.prompt)
            )
            if ok and self.scheduler is not None:
                snap = self._preempted.get(req.rid)
                if snap is not None and snap["mode"] == "recompute":
                    # recompute-resume: replay the generated tokens to
                    # rebuild the KV bit-identically before continuing
                    row = next(
                        ix for ix, r in enumerate(server.active)
                        if r is not None and r.rid == req.rid
                    )
                    server.start_replay(row, req.out[1:])
                    del self._preempted[req.rid]
                self.scheduler.on_admitted(req.rid, time.monotonic())
            return ok
        block = self.kvseg[
            rank, slot * self.block_elems : (slot + 1) * self.block_elems
        ]
        header = block[: self.HEADER].view(np.int32)
        tok, position = int(header[0]), int(header[1])
        caches_one = self.layout.unflatten(self.jnp.asarray(block[self.HEADER :]))
        return server.admit_prefilled(
            req, caches_one, first_token=tok, position=position
        )

    # ------------------------------------------------------------------ #
    # fault tolerance: heartbeats, death recovery, elastic scale-out
    # ------------------------------------------------------------------ #
    def kill_rank(self, rank: int) -> None:
        """Fault injection: rank ``rank`` stops beating, computing, and
        acknowledging from this instant.  Its segment mirror is poisoned
        with NaN so any recovery path that erroneously consumes a "dead"
        rank's bytes breaks token parity instead of silently passing.
        Detection is automatic within ``heartbeat_timeout`` ticks."""
        if not self.paged:
            raise ValueError("fault injection requires paged=True")
        if not (0 <= rank < self.n):
            raise ValueError(f"rank {rank} outside the {self.n}-rank ring")
        self.killed.add(rank)
        self.kvseg[rank, :] = np.nan

    def _heartbeat(self) -> None:
        """Tick-clocked liveness: every live rank beats once per tick (on
        a real cluster the beat is an AM to the coordinator); the monitor
        declares a silent rank dead after ``heartbeat_timeout`` missed
        ticks and recovery runs before any scheduling decision."""
        if not self.paged:
            return
        tr = obs_trace.active()
        for r in range(self.n):
            if r in self.killed or r in self.dead_ranks:
                continue
            if self.beat_filter is not None and not self.beat_filter(
                r, self._tick_no
            ):
                if tr.enabled:
                    tr.instant("heartbeat_miss", cat="ft", rank=r)
                continue
            self.monitor.beat(r)
        for r in self.monitor.check():
            self._on_rank_failed(r)

    def _on_rank_failed(self, rank: int) -> None:
        if rank in self.dead_ranks:
            return
        self.dead_ranks.add(rank)
        self.rank_failures += 1
        role = self.roles[rank]
        tr = obs_trace.active()
        if tr.enabled:
            tr.instant("rank_death", cat="ft", rank=rank, role=role)
            # flight recorder: freeze the last few ticks of the ring at
            # the moment of death, before recovery mutates anything
            self.flight_dumps.append(
                obs_export.flight_dump(
                    tr,
                    self.flight_ticks,
                    reason=f"rank {rank} ({role}) died",
                    rank=rank,
                )
            )
        if role == "decode":
            g = next(
                g for g, lead in enumerate(self.group_leaders)
                if lead <= rank < lead + self.tp
            )
            self._recover_decode(g)
        elif role == "memory":
            self._recover_memory(rank - self._memory_base)
        elif role == "prefill":
            self._recover_prefill(rank)
        # spares are idle: nothing to recover
        self._rebuild_plans()

    def _to_recompute(self, rid: int) -> None:
        """Route a request whose pages (pool or tier) died through the
        bit-exact recompute-resume path: re-prefill, replay the generated
        history, continue — the tokens already streamed are kept."""
        req = self.by_rid[rid]
        snap = self._preempted.get(rid)
        if snap is None:
            self._preempted[rid] = {
                "mode": "recompute",
                "position": 0,
                "last_token": 0,
                "n_mat": 0,
                "swapped": False,
                "replay": [],
            }
            if self.scheduler is not None:
                self.scheduler.on_preempted(rid, "recompute")
        else:
            snap["mode"] = "recompute"
            snap["swapped"] = False
            snap.pop("staged", None)
        if req not in self.queue:
            self.queue.append(req)
        self.recovered_recompute += 1

    def _recover_decode(self, g: int) -> None:
        """Decode group ``g`` died: re-route its in-flight admissions,
        convert its resident requests to recompute-resume, re-stage its
        pending tier restores to surviving groups, and retire its pool
        shard.  Detection-to-recovery is one host step — the surviving
        ranks never stall."""
        from repro.serving import pool as pool_lib

        self.dead_groups.add(g)
        server = self.decode_servers[g]
        lead = self.decode_rank(g)
        # in-flight pushes targeting the dead group re-route: the pages
        # never became visible to a live rank (acks from a dead group are
        # voided), so the request re-enters the queue; the prefill token
        # it already produced is kept, so re-admission elsewhere is
        # bit-exact
        for p, push in enumerate(self.pending_push):
            if push is not None and push[1] == g:
                self.pending_push[p] = None
                self.queue.append(push[0])
                self.recovered_reroutes += 1
        self.staged[g].clear()
        self.inbox[lead] = 0
        # completion AMs the dead group can no longer send
        self._done_queue = [e for e in self._done_queue if e[0] != g]
        # staged swap-outs FROM the dead group: the victim's pages lived
        # in its (now lost) pool shard — release the planned tier slots
        # and recompute
        for job in [j for j in self._swap_jobs if j[1] == g]:
            self._swap_jobs.remove(job)
            rid = job[0]
            if self.tier is not None and rid in self.tier.holdings:
                self.tier.release(rid)
            self._to_recompute(rid)
        # staged fetches INTO the dead group: the tier copy survives
        # (holdings release only at install) — re-stage to a live group
        for job in [j for j in self._fetch_jobs if j[1] == g]:
            self._fetch_jobs.remove(job)
            if job[0] >= 0:
                self._preempted[job[0]]["staged"] = False
        # prefix migrations SOURCED at the dead group: the donor bytes
        # never arrived — drop the target's adopted-but-empty pages
        for job in [
            j for j in self._fetch_jobs if j[0] < 0 and j[4] == lead
        ]:
            self._fetch_jobs.remove(job)
            self.stores[job[1]].release_prefix_cache()
            self._pending_migration = None
        # restored-but-not-installed requests on the dead group: same
        # re-stage (their pool copy died with the shard)
        for rid, d in list(self._installable.items()):
            if d == g:
                del self._installable[rid]
                self._preempted[rid]["staged"] = False
        # resident rows recover through recompute-resume replay
        for i, r in enumerate(server.active):
            if r is None:
                continue
            server.evict_row(i)
            self._to_recompute(r.rid)
        for req in list(server.queue):
            server.queue.remove(req)
            if req not in self.queue:
                self.queue.append(req)
        # fresh (empty) shard bookkeeping so survivor invariants hold and
        # nothing references the lost pages
        self.stores[g] = pool_lib.PagedKVStore(
            self.shard_layout, self.pages_per_rank
        )
        server.store = self.stores[g]
        self._alias_store_mem()

    def _recover_memory(self, m: int) -> None:
        """Memory rank ``m`` died: scrub its tier placements.  Requests
        with a surviving replica leg restore from it (the quorum read);
        requests whose last copy died fall back to recompute-resume."""
        mrank = self.memory_rank(m)
        handled: set = set()
        # staged swap-outs with a leg on the dead rank: drop that leg;
        # a job with no live leg left converts to recompute
        for job in list(self._swap_jobs):
            rid, d, src, legs = job
            live = tuple(leg for leg in legs if leg[0] != mrank)
            if len(live) == len(legs):
                continue
            if live:
                self._swap_jobs[self._swap_jobs.index(job)] = (
                    rid, d, src, live,
                )
            else:
                self._swap_jobs.remove(job)
                if not self._group_down(d):
                    self.stores[d].evict_request(rid)
                if rid in self.tier.holdings:
                    self.tier.release(rid)
                self._to_recompute(rid)
                handled.add(rid)
        # staged fetches sourced at the dead rank: undo the target-side
        # resume allocation; the re-stage picks a surviving leg
        for job in [
            j for j in self._fetch_jobs if j[0] >= 0 and j[4] == mrank
        ]:
            self._fetch_jobs.remove(job)
            rid, d = job[0], job[1]
            if not self._group_down(d):
                self.stores[d].evict_request(rid)
            self._preempted[rid]["staged"] = False
        lost = self.tier.mark_failed(m)
        for rid in lost:
            if rid in handled:
                continue
            if rid in self._installable:
                continue  # restored copy already safe in a pool shard
            self._to_recompute(rid)

    def _recover_prefill(self, p: int) -> None:
        """Prefill worker ``p`` died: its in-flight push (if any) is
        undone on the live target and the request re-queued for a
        surviving worker (the prefill is recomputed — still bit-exact,
        prefill is deterministic)."""
        push = self.pending_push[p]
        if push is None:
            return
        req, d, slot, _, _ = push
        self.pending_push[p] = None
        if self.paged and not self._group_down(d):
            self.stores[d].evict_request(req.rid)
        self.staged[d].pop(slot, None)
        if req not in self.queue:
            self.queue.append(req)
        self.recovered_reroutes += 1

    def _rebuild_plans(self) -> None:
        """Re-plan the collective schedules for the surviving engine map:
        a dead rank's engine leaves the cost model, so segment counts and
        batching re-derive from the ranks that remain (ACCL+-style
        re-planning on membership change).  The jitted transfer cache is
        dropped — its programs closed over the stale plans."""
        from repro.core import engine as engine_lib
        from repro.core import sched

        alive = tuple(
            b for r, b in enumerate(self._backends)
            if r not in self.dead_ranks
        )
        if not alive:
            return
        engine = engine_lib.make_engine(alive, self.node_axis, len(alive))
        if self.paged:
            self.plan = sched.plan_p2p(
                nbytes=self.shard_layout.page_bytes,
                engine=engine, costs=self.costs,
            )
            self.swap_plan = sched.plan_p2p(
                nbytes=self.max_swap * self.playout.page_bytes,
                engine=engine, costs=self.costs,
            )
        else:
            self.plan = sched.plan_p2p(
                nbytes=self.block_bytes, engine=engine, costs=self.costs,
            )
        self._transfer_fns.clear()

    def join_decode_rank(self) -> int:
        """Elastic scale-out: promote an idle spare rank into a NEW
        decode group (``launch.mesh.promote_spare`` regenerates the role
        map; the ring size never changes, so every permutation and
        segment shape stays valid).  The joined rank gets a fresh pool
        shard, and the busiest live group's prefix index migrates to it —
        index entries adopted host-side, page bytes shipped as ONE
        vectored RMA get on the swap plane.  Returns the promoted rank."""
        from repro.launch.serve import PooledDecodeServer
        from repro.serving import pool as pool_lib

        if not self.paged or self.tp != 1:
            raise ValueError("elastic join requires paged=True and tp == 1")
        spare = next(
            (
                r for r, role in enumerate(self.roles)
                if role == "spare"
                and r not in self.killed
                and r not in self.dead_ranks
            ),
            None,
        )
        if spare is None:
            raise RuntimeError("no live spare rank to promote")
        self.roles = mesh_lib.promote_spare(self.roles, spare, to="decode")
        g = self.n_groups
        self.group_leaders.append(spare)
        store = pool_lib.PagedKVStore(self.shard_layout, self.pages_per_rank)
        self.stores.append(store)
        self.shard_mems.append([None])
        self.staged.append({})
        self._finished_seen.append(0)
        self.n_groups += 1
        self.decode_servers.append(
            PooledDecodeServer(
                self.model, self.ctx, self._params_on(spare),
                self._decode_batch, self.cache_len, store=store,
                eos_id=self._eos_id,
                on_page_shortage=(
                    lambda rid, need, g=g:
                    self._decode_shortage(g, rid, need)
                ),
            )
        )
        self.decode_servers[-1].trace_rank = spare
        self._alias_store_mem()
        self.elastic_joins += 1
        tr = obs_trace.active()
        if tr.enabled:
            tr.instant("elastic_join", cat="ft", rank=spare, group=g)
        # prefix-index migration: warm the new shard from the live group
        # holding the largest index so affinity routing can target it
        donor, best = None, 0
        for d in range(self.n_groups - 1):
            if self._group_down(d):
                continue
            n = len(self.stores[d].prefix_entries())
            if n > best:
                donor, best = d, n
        if donor is not None and self._pending_migration is None:
            entries = self.stores[donor].prefix_entries()[: self.max_swap]
            pairs = store.adopt_prefix(entries)
            if pairs:
                self.stores[donor].pin_pages([dp for dp, _ in pairs])
                remote = [
                    dp * self.playout.page_elems for dp, _ in pairs
                ]
                local = [
                    lp * self.playout.page_elems for _, lp in pairs
                ]
                self._fetch_jobs.append(
                    (-1, g, remote, local, self.decode_rank(donor))
                )
                self._pending_migration = {
                    "donor": donor, "n": len(pairs),
                }
        return spare

    # ------------------------------------------------------------------ #
    def tick(self) -> None:
        """One cluster tick: prefill (possibly preempting for the queue
        head), stage resumes, launch the KV transfer (admission puts +
        swap puts + swap-in gets + AM control plane), overlap a decode
        step with it, consume the results, and install restored
        requests."""
        self._tick_no += 1
        tr = obs_trace.active()
        tr.set_tick(self._tick_no)
        if self.fault_hook is not None:
            self.fault_hook(self, "tick", self._tick_no)
        with tr.span("tick", cat="tick"):
            with tr.span("heartbeat", cat="tick_phase"):
                self._heartbeat()
            with tr.span("prefill", cat="tick_phase"):
                self._run_prefills()
            with tr.span("resume_stage", cat="tick_phase"):
                self._run_resumes()
            with tr.span("transfer_launch", cat="tick_phase"):
                results = self._launch_transfer()
            with tr.span("decode", cat="tick_phase"):
                self._decode_step()  # overlaps the in-flight transfer
            if self.fault_hook is not None:
                # fires between transfer launch and consume: a kill here
                # lands AFTER the put went on the wire but BEFORE its
                # kv_ready ack is processed — the mid-handoff death window
                self.fault_hook(self, "pre_consume", self._tick_no)
            if results is not None:
                with tr.span("transfer_consume", cat="tick_phase"):
                    self._consume_transfer(results)
            with tr.span("install", cat="tick_phase"):
                self._apply_decode_writes()
                if self.paged and self.tier is not None:
                    self._install_resumed()
            if self.health is not None:
                # live SLO projections over everything still tracked;
                # the rendered one-liner rides the trace so a flight dump
                # shows cluster health next to the phase spans it explains
                with tr.span("health", cat="tick_phase"):
                    self.health.tick(
                        self._tick_no, time.monotonic(),
                        progress={
                            r.rid: len(r.out)
                            for s in self.decode_servers
                            for r in s.active if r is not None
                        },
                    )
                    if tr.enabled:
                        tr.instant(
                            "health_summary", cat="slo",
                            line=self.health.render(),
                        )

    def idle(self) -> bool:
        return (
            not self.queue
            and all(p is None for p in self.pending_push)
            and not any(self.staged[d] for d in range(self.n_groups))
            and not any(any(s.active) or s.queue for s in self.decode_servers)
            and not self._preempted
            and not self._swap_jobs
            and not self._fetch_jobs
            and not self._installable
            and self._inflight_swap is None
            and self._inflight_fetch is None
            and self._pending_migration is None
        )

    def _latencies(self) -> Tuple[List[float], List[float]]:
        """Per-request (latency, ttft) lists, preferring trace-derived
        numbers: when tracing is on and every finished request's
        lifecycle instants (``req_submit`` / ``req_first_token`` /
        ``req_retire``) are still in the ring, TTFT and latency come
        from :meth:`~repro.obs.trace.Tracer.request_stats`.  Otherwise
        (tracing off, or the ring evicted early events) the Request
        wall timers are the fallback."""
        tr = obs_trace.active()
        if tr.enabled and self.finished:
            per = tr.request_stats()
            lat = [
                per[r.rid]["latency_s"] for r in self.finished
                if r.rid in per and "latency_s" in per[r.rid]
            ]
            ttft = [
                per[r.rid]["ttft_s"] for r in self.finished
                if r.rid in per and "ttft_s" in per[r.rid]
            ]
            if len(lat) == len(self.finished) == len(ttft):
                return lat, ttft
        lat = [r.t_done - r.t_enqueue for r in self.finished]
        ttft = [r.t_first - r.t_enqueue for r in self.finished]
        return lat, ttft

    def reset_metrics(self) -> None:
        """Zero the cluster's cumulative counters (scheduler and tier
        share the registry, so theirs clear too); gauges survive."""
        self.metrics.reset()

    def stats(self) -> Dict[str, Any]:
        """Cumulative counters and point-in-time gauges — everything in
        :meth:`run_until_drained`'s dict except the run-scoped rates and
        latencies.  Counter values read off the typed registry; the
        derived gauges (free pages, prefix hit rate) are published onto
        it here so a registry ``snapshot()`` sees them too."""
        if self.paged:
            kv_bytes = self.kv_pages_sent * self.playout.page_bytes
        else:
            kv_bytes = self.kv_transfers * self.block_bytes
        stats = {
            "requests": len(self.finished),
            "decoded_tokens": self.decoded_tokens,
            "kv_transfers": self.kv_transfers,
            "kv_acked": self.kv_acked,
            "kv_bytes": kv_bytes,
            "kv_block_bytes": self.block_bytes,
            "kv_plan": self.plan.describe(),
            "completions_notified": int(self.done[: self.n_prefill].sum()),
            "am_dropped": self.dropped_am,
        }
        if self.paged:
            # hit rate over SHAREABLE pages only (full prompt pages — the
            # store's counters); tail pages can never be shared and would
            # dilute the number
            hits = sum(s.prefix_hits for s in self.stores)
            misses = sum(s.prefix_misses for s in self.stores)
            hit_rate = hits / (hits + misses) if hits + misses else 0.0
            free_pages = sum(s.n_free for s in self.stores)
            self.metrics.gauge("pool_free_pages").set(free_pages)
            self.metrics.gauge("prefix_hit_rate").set(hit_rate)
            stats.update({
                "paged": True,
                "tp": self.tp,
                "n_decode_groups": self.n_groups,
                "page_tokens": self.playout.page_tokens,
                "page_bytes": self.playout.page_bytes,
                "pages_per_rank": self.pages_per_rank,
                "kv_pages_sent": self.kv_pages_sent,
                "kv_pages_shared": self.kv_pages_shared,
                "prefix_hit_rate": hit_rate,
                "pool_free_pages": free_pages,
                "decode_paged_steps": sum(
                    getattr(s, "paged_decode_steps", 0)
                    for s in self.decode_servers
                ),
                "rank_failures": self.rank_failures,
                "recovered_recompute": self.recovered_recompute,
                "recovered_reroutes": self.recovered_reroutes,
                "elastic_joins": self.elastic_joins,
                "migrated_prefix_pages": self.migrated_prefix_pages,
                "heartbeat_failed": list(self.monitor.failed),
            })
            if self.scheduler is not None:
                stats.update(self.scheduler.stats())
            if self.health is not None:
                stats["slo_violations"] = int(
                    self.metrics.counter("slo_violations").value)
                stats["health"] = dict(self.health.last_summary)
            if self.tier is not None:
                stats.update(self.tier.stats())
                stats.update({
                    "n_memory_ranks": self.n_memory,
                    "swap_out_bytes": self.swap_out_bytes,
                    "swap_in_bytes": self.swap_in_bytes,
                    "swap_plan": self.swap_plan.describe(),
                })
        return stats

    def run_until_drained(self, max_ticks: int = 10000) -> Dict[str, Any]:
        t0 = time.monotonic()
        ticks = 0
        while not self.idle() and ticks < max_ticks:
            self.tick()
            ticks += 1
        # final flushes so the last completions reach their origin ranks
        # (bounded: an unacknowledged push must not spin forever)
        for _ in range(2 * self.n + 2):
            results = self._launch_transfer()
            if results is None:
                break
            self._consume_transfer(results)
        dt = time.monotonic() - t0
        lat, ttft = self._latencies()
        stats = self.stats()
        stats.update({
            "wall_s": dt,
            "ticks": ticks,
            "tok_per_s": self.decoded_tokens / dt if dt else 0.0,
            "p50_latency_s": float(np.median(lat)) if lat else 0.0,
            "p99_latency_s": float(np.percentile(lat, 99)) if lat else 0.0,
            "p50_ttft_s": float(np.median(ttft)) if ttft else 0.0,
            "kv_bytes_per_s": stats["kv_bytes"] / dt if dt else 0.0,
        })
        return stats
