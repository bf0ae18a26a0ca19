"""Role-based serving launcher: colocated or disaggregated prefill/decode.

Request lifecycle: queued -> prefilled (KV cache slot assigned) -> decoding
in the fixed-width decode batch -> finished (EOS or max tokens) -> slot
recycled for the next queued request.

The decode step is one jit'd ``model.decode_step`` over the whole batch;
per-row positions let rows be at different generation depths (continuous
batching).

Roles (``--role``):

- ``both`` (default) — the disaggregated cluster: a prefill pool and a
  decode pool as distinct GASNet ranks (``launch.mesh.serve_roles``, each
  pool optionally on its own engine via ``EngineMap``); finished KV caches
  cross over the GAS layer with ``sched.plan_p2p``-planned segmented puts
  and an AM request/reply control plane (``repro.serving.disagg``).
  Needs one device per rank.
- ``decode`` — the colocated path: one node prefills and decodes
  (:class:`Server` continuous batching, unchanged).  With ``--paged`` the
  KV cache lives in the global paged pool (:class:`PagedServer`): pages
  allocated and freed per admitted request, prompt prefixes shared by
  page table, token-identical to the dense server.
- ``prefill`` — the prefill pool alone: computes prefills and reports KV
  blocks/s, the feeder-side capacity number.

``--arch`` builds the architecture at its published widths with random
weights from a fixed seed; ``--smoke`` builds its small test preset
instead.  CPU-scale demo: ``python -m repro.launch.serve --smoke`` (with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` for N ranks).
"""
import argparse
import dataclasses
import functools
import time
from typing import Any, Dict, List, Optional

import numpy as np

from repro.obs import trace as obs_trace
from repro.obs.metrics import Registry


def _paged_decode_fn(model, ctx, layout):
    """Build the fused paged decode step: pool carrier -> decode views ->
    ``model.decode_step_paged`` -> carrier, all inside ONE jit.

    Keeping the reshape/bitcast chain on device (and, for the colocated
    :class:`PagedServer`, the carrier itself resident across ticks) removes
    the per-tick host round trip over the whole pool that made the paged
    decode path slower than the dense baseline.  The carrier has one extra
    *scratch* row past the pool: dead rows and unmaterialised table slots
    scatter there, and it is wiped every step so garbage never accumulates.
    """
    import jax
    import jax.numpy as jnp

    empty_row = np.asarray(layout.empty_page_row())

    @jax.jit
    def step(params, token, positions, mem, tables):
        mem = mem.at[mem.shape[0] - 1].set(
            jnp.asarray(empty_row, mem.dtype)
        )
        views = layout.decode_views(mem)
        logits, views = model.decode_step_paged(
            params, ctx, token, positions, views, tables
        )
        return logits, layout.views_to_pool(views)

    return step


def _paged_decode_views_fn(model, ctx, layout):
    """The colocated variant of :func:`_paged_decode_fn`: the pool stays
    resident ON DEVICE in *decode-views* form (the per-layer page-pool
    pytree) across ticks, so a steady-state step runs zero carrier
    repacks — the carrier<->views conversion happens only at host sync
    points.  The views buffers are donated, and the decode step carries
    them through its layer loop: each layer's token scatter updates the
    pool in place, with no copy of the pool or of a layer of it."""
    import jax
    import jax.numpy as jnp

    empty_views = layout.decode_views(
        jnp.asarray(np.asarray(layout.empty_page_row())[None])
    )

    @functools.partial(jax.jit, donate_argnums=(3,))
    def step(params, token, positions, views, tables):
        # wipe the scratch page (page axis 1 of every (L, P, T, ...)
        # pool): dead rows and unmaterialised slots scattered garbage
        # into it last step
        views = jax.tree_util.tree_map(
            lambda pool, init: pool.at[:, pool.shape[1] - 1].set(
                init[:, 0]
            ),
            views, empty_views,
        )
        logits, views = model.decode_step_paged(
            params, ctx, token, positions, views, tables
        )
        return logits, views

    return step


_PATCH_CHUNK = 8
_READ_CHUNK = 32


def _pool_patch_fn(layout):
    """Jitted device-side pool patch by page id for the views-resident
    pool: the pages ``fill_dst`` become the absent page (lazy
    materialisations) and ``copy_src -> copy_dst`` duplicate (COW
    splits).  Only page ids cross the host link.  All index operands are
    fixed-width (:data:`_PATCH_CHUNK`), padded with the scratch page
    index so one compilation serves every patch."""
    import jax
    import jax.numpy as jnp

    empty_views = layout.decode_views(
        jnp.asarray(np.asarray(layout.empty_page_row())[None])
    )

    @functools.partial(jax.jit, donate_argnums=(0,))
    def patch(views, fill_dst, copy_src, copy_dst):
        def apply(pool, init):
            # a page materialised this tick is held by one table alone,
            # so no copy sources it: the order of the two is free
            pool = pool.at[:, fill_dst].set(init)
            return pool.at[:, copy_dst].set(pool[:, copy_src])

        return jax.tree_util.tree_map(apply, views, empty_views)

    return patch


def _pool_upload_fn(layout):
    """Jitted write of carrier ``rows`` at pages ``dst`` of the
    views-resident pool: the host mirror goes back up after a host sync,
    a :data:`_PATCH_CHUNK` of pages at a time (padded with the scratch
    page), never as one float32 carrier of the whole pool."""
    import jax

    @functools.partial(jax.jit, donate_argnums=(0,))
    def upload(views, dst, rows):
        return jax.tree_util.tree_map(
            lambda pool, rv: pool.at[:, dst].set(rv),
            views, layout.decode_views(rows),
        )

    return upload


def _admit_pages_fn(layout):
    """Jitted write of an admission's fresh prompt pages into the
    views-resident pool, straight from the prefill's cache on the
    device: every ``(L, 1, cache_len, *tail)`` cache leaf is cut into
    ``(L, n_pages, page_tokens, *tail)`` pages and logical page
    ``src[j]`` lands at physical page ``dst[j]``.  No payload crosses
    the host link.  ``src``/``dst`` are ``n_pages`` wide, padded with the
    scratch page as ``dst``, so one compilation serves every admission."""
    import jax

    T = layout.page_tokens

    @functools.partial(jax.jit, donate_argnums=(0,))
    def admit_pages(views, caches, src, dst):
        def put(pool, leaf):
            pages = leaf.reshape(
                (leaf.shape[0], -1, T) + tuple(leaf.shape[3:])
            )
            return pool.at[:, dst].set(pages[:, src].astype(pool.dtype))

        return jax.tree_util.tree_map(put, views, caches)

    return admit_pages


def _pool_read_fn(layout):
    """Jitted read of ``idx`` pages of the views-resident pool back into
    carrier rows ``(len(idx), page_elems)``: the host sync reads the pool
    a chunk at a time, so the float32 carrier of the whole pool is never
    materialised on the device."""
    import jax

    @jax.jit
    def read(views, idx):
        return layout.views_to_pool(
            jax.tree_util.tree_map(lambda pool: pool[:, idx], views)
        )

    return read


def _pool_write_need(store, layout, rid: int, position: int) -> int:
    """Fresh pages the next decode write needs: one when the position
    lands on an unmaterialised slot (lazy growth) or a shared page
    (copy-on-write split), none otherwise."""
    table = store.tables[rid]
    p = table[position // layout.page_tokens]
    if p < 0:
        return 1
    return 1 if store.state.refcnt[p] > 1 else 0


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new: int = 16
    out: List[int] = dataclasses.field(default_factory=list)
    t_enqueue: float = 0.0
    t_first: float = 0.0
    t_done: float = 0.0
    slo: Any = None  # Optional[repro.serving.scheduler.SLO]


class Server:
    """Fixed-decode-batch continuous batching over Model prefill/decode."""

    def __init__(self, model, ctx, params, batch_size: int, cache_len: int,
                 eos_id: int = -1, greedy: bool = True, seed: int = 0):
        import jax
        import jax.numpy as jnp

        self.jax, self.jnp = jax, jnp
        self.model = model
        self.ctx = ctx
        self.params = params
        self.B = batch_size
        self.cache_len = cache_len
        self.eos_id = eos_id
        self.greedy = greedy
        self.key = jax.random.PRNGKey(seed)

        # rank attributed to this server's trace events (the disagg
        # cluster sets it to the decode rank; standalone servers trace
        # on the program-wide row)
        self.trace_rank: Optional[int] = None
        self.active: List[Optional[Request]] = [None] * batch_size
        self.positions = np.zeros((batch_size,), np.int32)
        self.last_token = np.zeros((batch_size, 1), np.int32)
        self.caches = None  # lazily built from first prefill
        self.queue: List[Request] = []
        self.finished: List[Request] = []
        # slot -> remaining tokens a recompute-resume must replay: the
        # decode path reproduces them bit-identically (same ops, same
        # inputs), rebuilding the KV cache without re-appending output
        self.replaying: Dict[int, List[int]] = {}

        self._decode = jax.jit(
            lambda p, t, pos, c: model.decode_step(p, ctx, t, pos, c)
        )
        self._prefill_one = jax.jit(
            lambda p, b: model.prefill(p, ctx, b, cache_len=cache_len)
        )

    # ------------------------------------------------------------------ #
    def submit(self, req: Request) -> None:
        req.t_enqueue = time.monotonic()
        tr = obs_trace.active()
        if tr.enabled:
            tr.instant("req_submit", cat="req", rank=self.trace_rank,
                       rid=req.rid, prompt_len=len(req.prompt))
        self.queue.append(req)

    def _free_slot(self) -> Optional[int]:
        for i, r in enumerate(self.active):
            if r is None:
                return i
        return None

    def _write_row(self, caches_one, slot: int) -> None:
        """Insert a single-request cache into batch row ``slot``."""
        jnp = self.jnp
        if self.caches is None:
            # build an empty batched cache from the single-row structure
            self.caches = self.jax.tree.map(
                lambda x: jnp.zeros((x.shape[0], self.B) + x.shape[2:],
                                    x.dtype),
                caches_one,
            )
        self.caches = self.jax.tree.map(
            lambda full, one: full.at[:, slot].set(one[:, 0]),
            self.caches, caches_one,
        )

    def admit_prefilled(
        self, req: Request, caches_one, first_token: int, position: int
    ) -> bool:
        """Install an externally prefilled request (the disaggregated
        handoff target: the KV cache arrived over the GAS layer, the
        first token and position rode in the block header).  Returns
        False when no decode row is free — the caller keeps the block
        staged and retries next tick."""
        slot = self._free_slot()
        if slot is None:
            return False
        if not req.out:
            req.out.append(int(first_token))
        tr = obs_trace.active()
        if not req.t_first:
            req.t_first = time.monotonic()
            if tr.enabled:
                tr.instant("req_first_token", cat="req",
                           rank=self.trace_rank, rid=req.rid)
        if tr.enabled:
            tr.instant("req_admit", cat="req", rank=self.trace_rank,
                       rid=req.rid, slot=slot, position=position)
        self.active[slot] = req
        self.positions[slot] = position
        self.last_token[slot, 0] = int(first_token)
        self._write_row(caches_one, slot)
        return True

    def _admit(self) -> None:
        while self.queue:
            if self._free_slot() is None:
                return
            req = self.queue.pop(0)
            toks = self.jnp.asarray(req.prompt, self.jnp.int32)[None]
            logits, caches_one = self._prefill_one(
                self.params, {"inputs": toks}
            )
            tok = int(np.argmax(np.asarray(logits)[0]))
            self.admit_prefilled(
                req, caches_one, first_token=tok, position=len(req.prompt)
            )

    def _retire(self, slot: int) -> None:
        req = self.active[slot]
        if req is None:  # already retired this step (eos at the cache cap)
            return
        req.t_done = time.monotonic()
        tr = obs_trace.active()
        if tr.enabled:
            tr.instant("req_retire", cat="req", rank=self.trace_rank,
                       rid=req.rid, tokens=len(req.out))
        self.finished.append(req)
        self.active[slot] = None
        self._release(req)

    def evict_row(self, slot: int) -> Optional[Request]:
        """Remove a request from its decode row WITHOUT retiring it (the
        preemption path): the caller owns its KV state (swap or discard)
        and re-admits it later.  No release hook runs."""
        req = self.active[slot]
        self.active[slot] = None
        self.replaying.pop(slot, None)
        return req

    def start_replay(self, slot: int, tokens: List[int]) -> None:
        """Arm a recompute-resume: the next ``len(tokens)`` decode steps
        on ``slot`` rebuild the KV cache by re-deriving exactly those
        tokens (asserted — the decode path is deterministic), without
        re-appending them to the request's output."""
        if tokens:
            self.replaying[slot] = list(tokens)

    # -- paged-pool hooks (no-ops for the dense server) ----------------- #
    def _post_decode(self, live: List[int], written: Dict[int, int]) -> None:
        """Called after one decode step, before retirement: ``written``
        maps each live row to the cache position the step wrote."""

    def _release(self, req: Request) -> None:
        """Called when a request leaves its decode row."""

    def _advance(self, live: List[int], logits: np.ndarray) -> None:
        """Shared post-decode token handling: append/advance each live
        row, replaying preempted-and-recomputed rows without appending."""
        for i in live:
            req = self.active[i]
            tok = int(np.argmax(logits[i]))
            replay = self.replaying.get(i)
            if replay:
                expect = replay.pop(0)
                if tok != expect:
                    raise AssertionError(
                        f"recompute replay diverged on rid {req.rid}: "
                        f"step produced {tok}, original was {expect}"
                    )
                if not replay:
                    del self.replaying[i]
                self.positions[i] += 1
                self.last_token[i, 0] = tok
                continue  # the token is already in req.out
            req.out.append(tok)
            self.positions[i] += 1
            self.last_token[i, 0] = tok
            if tok == self.eos_id or len(req.out) >= req.max_new:
                self._retire(i)
            if self.positions[i] >= self.cache_len - 1:
                self._retire(i)

    # ------------------------------------------------------------------ #
    def step(self) -> int:
        """One scheduler tick: admit, decode one token for all rows.
        Subclasses override :meth:`_step` and :meth:`_after_step`; this
        wrapper is the single place every server's tick gets its
        ``serve.tick`` span."""
        with obs_trace.span("serve.tick", cat="decode",
                            rank=self.trace_rank) as sp:
            n = self._step()
            self._after_step()
            if sp is not None:
                sp.args["live"] = n
        return n

    def _after_step(self) -> None:
        """Called at the end of every tick, inside its span."""

    def _step(self) -> int:
        self._admit()
        live = [i for i, r in enumerate(self.active) if r is not None]
        if not live or self.caches is None:
            return 0
        jnp = self.jnp
        logits, self.caches = self._decode(
            self.params,
            jnp.asarray(self.last_token),
            jnp.asarray(self.positions),
            self.caches,
        )
        logits = np.asarray(logits)
        self._post_decode(live, {i: int(self.positions[i]) for i in live})
        self._advance(live, logits)
        return len(live)

    def _pending(self) -> bool:
        return bool(self.queue) or any(r is not None for r in self.active)

    def run_until_drained(self, max_ticks: int = 10000) -> Dict[str, Any]:
        t0 = time.monotonic()
        decoded = 0
        ticks = 0
        while self._pending() and ticks < max_ticks:
            decoded += self.step()
            ticks += 1
        dt = time.monotonic() - t0
        lat = [r.t_done - r.t_enqueue for r in self.finished]
        ttft = [r.t_first - r.t_enqueue for r in self.finished]
        return {
            "requests": len(self.finished),
            "decoded_tokens": decoded,
            "wall_s": dt,
            "tok_per_s": decoded / dt if dt else 0.0,
            "p50_latency_s": float(np.median(lat)) if lat else 0.0,
            "p50_ttft_s": float(np.median(ttft)) if ttft else 0.0,
        }


class PagedServer(Server):
    """Continuous batching over the paged KV pool (``repro.serving.pool``)
    with SLO-aware preemptive scheduling over a tiered KV memory.

    The dense server hands each admitted request a private cache row; the
    paged server instead allocates fixed-size token *pages* from a
    refcounted pool per admitted request and frees them when the request
    retires.  Requests sharing a prompt prefix resolve to the *same
    physical pages* (copy-on-write protected), so a warm prefix costs no
    page storage — and, in the disaggregated cluster, no transfer bytes.

    With ``paged_decode=True`` (default) the decode step itself runs
    THROUGH the page table — the new token's K/V scatter straight into
    the pool and attention is ``kernels.paged_attention`` over the
    physical pages; no dense per-request cache row exists anywhere.
    Admission is **lazy** (only prompt pages materialise; the generation
    tail allocates page by page as positions are written), so the pool
    *oversubscribes*: when the free list runs dry the
    :class:`~repro.serving.scheduler.AdmissionScheduler` preempts victims
    — swap (pages copied to the :class:`~repro.serving.tier.MemoryTier`,
    restored bit-exactly at resume) or recompute (pages dropped; resume
    replays the generated tokens, re-deriving them bit-identically),
    priced per victim by the measured β cost model.

    Token parity with :class:`Server` — pressured or not — is the
    correctness bar (asserted in the smoke demo and tests).

    Page payloads live in the device pool (``_dev_views``); the store's
    ``mem`` host mirror is authoritative only between a host sync
    (preemption, swap, resume, drain) and the next upload.  While the
    device pool is live the host keeps bookkeeping alone: an admission
    writes its fresh prompt pages from the prefill's cache on the device
    (:func:`_admit_pages_fn`), lazy growth and COW splits patch pages by
    id (:func:`_pool_patch_fn`), and the decode step updates the pool in
    place.

    On the paged path each part of a tick's host work runs in a
    ``serve.*`` span (:func:`repro.obs.trace.span`: in the profiler's
    trace, beside the device's operations), and ``metrics`` counts what
    the tick moved, from host-known shapes: ``admissions`` (with
    recompute resumes), ``prefill_tokens``, ``prefill_cached_tokens``
    (prompt tokens on shared prefix pages), ``ticks`` and
    ``decode_rows`` (decode steps, and live rows summed over them),
    ``pages_written_on_device`` (fresh prompt pages written from a
    prefill's cache on the device), ``pages_filled_on_device`` (lazy
    growth pages made absent on the device), ``d2h_bytes`` (pool syncs
    to the host), ``h2d_bytes`` (the page ids of admission and patch
    programs, and pool re-uploads), ``host_syncs``, and the gauge
    ``pool_pages_peak`` (most pool pages in use at a decode step).
    """

    def __init__(self, model, ctx, params, batch_size: int, cache_len: int,
                 eos_id: int = -1, greedy: bool = True, seed: int = 0,
                 page_tokens: int = 8, n_pool_pages: Optional[int] = None,
                 paged_decode: bool = True, tier_slots: Optional[int] = None,
                 sched_costs: Optional[Dict[str, Any]] = None,
                 decode_step_us: float = 2000.0, prefill_us: float = 4000.0,
                 health: Optional[Any] = None):
        super().__init__(model, ctx, params, batch_size, cache_len,
                         eos_id=eos_id, greedy=greedy, seed=seed)
        from repro.serving.pool import PagedKVStore, PagedLayout
        from repro.serving.scheduler import AdmissionScheduler
        from repro.serving.tier import MemoryTier

        self.layout = PagedLayout.from_struct(
            model.kv_block_struct(ctx, prompt_len=4, cache_len=cache_len),
            cache_len=cache_len, page_tokens=page_tokens,
        )
        if n_pool_pages is None:
            n_pool_pages = (batch_size + 1) * self.layout.n_pages
        self.store = PagedKVStore(self.layout, n_pool_pages)
        self.paged_decode = paged_decode
        if tier_slots is None:
            tier_slots = max(
                n_pool_pages, batch_size * self.layout.n_pages
            )
        self.tier = MemoryTier(
            1, tier_slots, self.layout.page_elems, host_backed=True
        )
        self.scheduler = AdmissionScheduler(
            page_bytes=self.layout.page_bytes, costs=sched_costs,
            decode_step_us=decode_step_us, prefill_us=prefill_us,
        )
        # live SLO monitor (repro.obs.health.HealthMonitor): tracked per
        # submit, ticked per step; when its backpressure is enabled the
        # scheduler defers below-floor admissions while deadlines are at
        # risk.  Inert (risk 0) for requests without finite deadlines.
        self.health = health
        self._tick_no = 0
        if health is not None and getattr(health, "backpressure", False):
            self.scheduler.attach_health(health)
        self._by_rid: Dict[int, Request] = {}
        self._preempted: Dict[int, Dict[str, Any]] = {}
        self._decode_paged = _paged_decode_views_fn(model, ctx, self.layout)
        # device-resident pool in decode-views form (each per-layer pool
        # has P+1 rows, scratch last), kept across ticks; None whenever
        # the host mirror is authoritative
        self._dev_views = None
        # live high-water mark of page-table width (monotonic; each
        # growth is one fused-step recompile)
        self._table_width = 1
        # page mutations by id queued for the device-resident pool: lazy
        # materialisations (made absent) and COW src->dst splits, applied
        # by the jitted patch program before the next decode step (or
        # before any host sync)
        self._patch = _pool_patch_fn(self.layout)
        self._upload = _pool_upload_fn(self.layout)
        self._admit_pages = _admit_pages_fn(self.layout)
        self._read = _pool_read_fn(self.layout)
        self._pending_fills: List[int] = []
        self._pending_copies: List[tuple] = []
        self.metrics = Registry()
        for name in ("admissions", "prefill_tokens", "prefill_cached_tokens",
                     "ticks", "decode_rows", "pages_written_on_device",
                     "pages_filled_on_device", "d2h_bytes", "h2d_bytes",
                     "host_syncs"):
            self.metrics.counter(name)
        self.metrics.gauge("pool_pages_peak")

    def _has_pending(self) -> bool:
        return bool(self._pending_fills or self._pending_copies)

    def _apply_pending(self) -> None:
        """Flush the queued page fills and copies into the device-resident
        pool, by page id, :data:`_PATCH_CHUNK` ids a program."""
        with obs_trace.span("serve.patch"):
            P = self.store.state.n_pages  # scratch index pads the chunks
            fills, copies = self._pending_fills, self._pending_copies
            self._pending_fills, self._pending_copies = [], []
            self.metrics.counter("pages_filled_on_device").inc(len(fills))
            h2d = self.metrics.counter("h2d_bytes")
            while fills or copies:
                ids = np.full((3, _PATCH_CHUNK), P, np.int32)
                chunk, fills = fills[:_PATCH_CHUNK], fills[_PATCH_CHUNK:]
                ids[0, : len(chunk)] = chunk
                chunk, copies = copies[:_PATCH_CHUNK], copies[_PATCH_CHUNK:]
                if chunk:
                    ids[1:, : len(chunk)] = np.asarray(chunk, np.int32).T
                self._dev_views = self._patch(
                    self._dev_views, *(self.jnp.asarray(x) for x in ids))
                h2d.inc(ids.nbytes)

    def _sync_host(self) -> None:
        """Land the device-resident pool back in the host mirror before
        any host-side read or write of page payloads (swap staging,
        resume restores, bulk admission rewrites).  Queued page patches
        flush to the device first so the download is complete.  The
        device copy is dropped; the next decode step re-uploads the
        mutated mirror."""
        if self._dev_views is None:
            return
        with obs_trace.span("serve.sync_host"):
            if self._has_pending():
                self._apply_pending()
            P = self.store.state.n_pages
            d2h = self.metrics.counter("d2h_bytes")
            for lo in range(0, P, _READ_CHUNK):
                idx = np.minimum(
                    np.arange(lo, lo + _READ_CHUNK, dtype=np.int32), P
                )  # a ragged tail reads the scratch page
                rows = np.asarray(
                    self._read(self._dev_views, self.jnp.asarray(idx))
                )
                d2h.inc(rows.nbytes)
                self.store.mem[lo : lo + _READ_CHUNK] = rows[: P - lo]
            self._dev_views = None
            self.metrics.counter("host_syncs").inc()

    def _live_pages(self) -> np.ndarray:
        """Pages some request references; free pages hold nothing that
        is ever read (a page is rewritten whole when it is allocated)."""
        return np.flatnonzero(np.asarray(self.store.state.refcnt) > 0)

    # ------------------------------------------------------------------ #
    def submit(self, req: Request) -> None:
        from repro.serving.scheduler import SLO

        super().submit(req)
        self._by_rid[req.rid] = req
        self.scheduler.submit(
            req.rid, req.slo or SLO(), prompt_len=len(req.prompt),
            now=req.t_enqueue,
        )
        if self.health is not None:
            self.health.track(req.rid, req.slo or SLO(), req.t_enqueue)

    def _pending(self) -> bool:
        return super()._pending() or bool(self._preempted)

    # ------------------------------------------------------------------ #
    # capacity management: preemption + tiered swap
    # ------------------------------------------------------------------ #
    def _running_rids(self) -> List[int]:
        return [r.rid for r in self.active if r is not None]

    def _slot_of(self, rid: int) -> Optional[int]:
        for i, r in enumerate(self.active):
            if r is not None and r.rid == rid:
                return i
        return None

    def _freeable(self, rid: int) -> int:
        return self.store.freeable(rid)

    def _write_need(self, rid: int, position: int) -> int:
        return _pool_write_need(self.store, self.layout, rid, position)

    def _preempt(self, rid: int, mode: Optional[str] = None) -> None:
        from repro.serving import tier as tier_lib

        self._sync_host()  # swap staging reads page payloads
        slot = self._slot_of(rid)
        req = self._by_rid[rid]
        table = self.store.page_table(rid)
        logical = [lp for lp, pp in enumerate(table) if pp >= 0]
        chosen, swap_us, rec_us = self.scheduler.choose_mode(
            rid, len(logical))
        if mode is None:
            mode = chosen
        tr = obs_trace.active()
        if tr.enabled:
            tr.instant(
                "req_preempt", cat="req", rank=self.trace_rank, rid=rid,
                mode=mode, n_pages=len(logical),
                swap_est_us=round(swap_us, 1),
                recompute_est_us=round(rec_us, 1),
            )
        if mode == "swap":
            try:
                self.tier.plan_swap_out(rid, logical)
            except tier_lib.OutOfSlotsError:
                mode = "recompute"  # tier full: drop and replay instead
        if mode == "swap":
            rows = np.stack([self.store.mem[table[lp]] for lp in logical])
            self.tier.host_store(rid, rows)
        snap = {
            "mode": mode,
            "logical": tuple(logical),
            "position": int(self.positions[slot]),
            "last_token": int(self.last_token[slot, 0]),
            # a victim caught mid-replay must finish its replay after a
            # swap-resume (evict_row drops the row's replay state)
            "replay": list(self.replaying.get(slot, [])),
        }
        self.store.evict_request(rid)
        self.evict_row(slot)
        self._preempted[rid] = snap
        # keep the β model honest: replayed tokens are not new generation
        self.scheduler.entry(rid).generated = max(0, len(req.out) - 1)
        self.scheduler.on_preempted(rid, mode)

    def _make_room(self, need: int, beneficiary: int, strict: bool) -> bool:
        """Free at least ``need`` pool pages by preempting victims chosen
        by the scheduler; False when no eligible victim set suffices."""
        while self.store.n_free < need:
            victims = self.scheduler.pick_victims(
                self._running_rids(), need - self.store.n_free,
                self._freeable, beneficiary=beneficiary, strict=strict,
            )
            if not victims:
                return False
            for rid in victims:
                self._preempt(rid)
        return True

    # ------------------------------------------------------------------ #
    # admission + resume (scheduler-ordered)
    # ------------------------------------------------------------------ #
    def _bind_row(
        self, req: Request, slot: int, position: int, last_token: int
    ) -> None:
        tr = obs_trace.active()
        if not req.t_first:
            req.t_first = time.monotonic()
            if tr.enabled:
                tr.instant("req_first_token", cat="req",
                           rank=self.trace_rank, rid=req.rid)
            if self.health is not None:
                self.health.first_token(req.rid, req.t_first)
        if tr.enabled:
            tr.instant("req_admit", cat="req", rank=self.trace_rank,
                       rid=req.rid, slot=slot, position=position)
        self.active[slot] = req
        self.positions[slot] = position
        self.last_token[slot, 0] = int(last_token)

    def _prefill_pages(self, req: Request):
        """Prefill ``req``'s prompt; returns its first token and the
        prefill's cache, left on the device."""
        with obs_trace.span("serve.prefill"):
            toks = self.jnp.asarray(req.prompt, self.jnp.int32)[None]
            logits, caches_one = self._prefill_one(
                self.params, {"inputs": toks})
            tok = int(np.argmax(np.asarray(logits)[0]))
        return tok, caches_one

    def _admit_prompt(self, req: Request) -> int:
        """Prefill ``req``'s prompt into the pool (its leading full pages
        shared with resident prefixes, the tail left to lazy growth) and
        commit its page table; returns the first token.  The fresh prompt
        pages go from the prefill's cache into the device pool on the
        device (``serve.flatten``: the cache cut into pages there); the
        host does the bookkeeping alone (``serve.pool_write``)."""
        with obs_trace.span("serve.admit"):
            tok, caches = self._prefill_pages(req)
            self._ensure_dev_views()
            with obs_trace.span("serve.pool_write"):
                plan = self.store.admit_tables(req.rid, req.prompt)
            with obs_trace.span("serve.flatten"):
                P = self.store.state.n_pages
                ids = np.zeros((2, self.layout.n_pages), np.int32)
                ids[1] = P  # padding lands in the scratch page
                fresh = [(lp, pp) for lp, (pp, f) in enumerate(
                    zip(plan.table, plan.fresh)) if f]
                if fresh:
                    ids[:, : len(fresh)] = np.asarray(fresh, np.int32).T
                    self._dev_views = self._admit_pages(
                        self._dev_views, caches,
                        *(self.jnp.asarray(x) for x in ids))
                    self.metrics.counter("h2d_bytes").inc(ids.nbytes)
        self._count_admission(req, plan, len(fresh))
        return tok

    def _count_admission(self, req: Request, plan, written: int) -> None:
        m = self.metrics
        m.counter("admissions").inc()
        m.counter("prefill_tokens").inc(len(req.prompt))
        m.counter("prefill_cached_tokens").inc(
            len(plan.shared) * self.layout.page_tokens)
        m.counter("pages_written_on_device").inc(written)

    def _resume(self, rid: int, slot: int) -> bool:
        st = self._preempted[rid]
        req = self._by_rid[rid]
        self._sync_host()  # restores / re-prefills write page payloads
        if st["mode"] == "swap":
            if self.store.n_free < len(st["logical"]):
                return False
            phys = self.store.admit_resume(rid, st["logical"])
            rows = self.tier.host_load(rid)
            self.tier.release(rid)
            for row, pp in zip(rows, phys):
                self.store.mem[pp] = row
            self._bind_row(req, slot, st["position"], st["last_token"])
            self.start_replay(slot, st.get("replay", []))
        else:  # recompute: re-prefill the prompt, replay the generation
            if self.store.n_free < self.layout.pages_for(len(req.prompt)):
                return False
            self._admit_prompt(req)
            self._bind_row(req, slot, len(req.prompt), req.out[0])
            self.start_replay(slot, req.out[1:])
        del self._preempted[rid]
        tr = obs_trace.active()
        if tr.enabled:
            tr.instant("req_resume", cat="req", rank=self.trace_rank,
                       rid=rid, slot=slot, mode=st["mode"])
        self.scheduler.on_admitted(rid, time.monotonic())
        return True

    def _admit(self) -> None:
        if not self.paged_decode:
            return self._admit_dense()
        for rid in self.scheduler.admission_order():
            slot = self._free_slot()
            if slot is None:
                return
            if rid in self._preempted:
                self._resume(rid, slot)
                continue
            req = self._by_rid.get(rid)
            if req is None or req not in self.queue:
                continue
            need = self.layout.pages_for(len(req.prompt))
            if self.store.n_free < need and not self._make_room(
                need, rid, strict=True
            ):
                continue
            self.queue.remove(req)
            tok = self._admit_prompt(req)
            if not req.out:
                req.out.append(tok)
            self._bind_row(req, slot, len(req.prompt), req.out[0])
            self.scheduler.on_admitted(rid, time.monotonic())

    def _admit_dense(self) -> None:
        """The PR-4 path (``paged_decode=False``): full-table admission,
        decode on dense rows gathered through the page table."""
        while self.queue:
            if self._free_slot() is None:
                return
            # conservative gate: admission may need a full table of pages
            if self.store.n_free < self.layout.n_pages:
                return
            req = self.queue.pop(0)
            tok, caches = self._prefill_pages(req)
            pages = np.asarray(self.layout.flatten(caches))
            self.metrics.counter("d2h_bytes").inc(pages.nbytes)
            self.store.admit(req.rid, req.prompt, pages)
            # the decode row is read back THROUGH the page table, so the
            # pool (not the prefill output) is the source of truth
            self.admit_prefilled(
                req, self.store.gather(req.rid),
                first_token=tok, position=len(req.prompt),
            )
            self.scheduler.on_admitted(req.rid, time.monotonic())

    # ------------------------------------------------------------------ #
    # the end-to-end paged decode step
    # ------------------------------------------------------------------ #
    def _step(self) -> int:
        if not self.paged_decode:
            return super()._step()
        self._admit()
        live = [i for i, r in enumerate(self.active) if r is not None]
        if not live:
            return 0
        # write capacity row by row: lazy materialisation / COW splits may
        # need fresh pages — the oversubscription pressure point.  Each
        # row claims its page immediately after the capacity check (no
        # under-reservation across rows); a row that cannot get one (even
        # after preempting eligible victims) self-preempts and resumes
        # once pages free up.
        from repro.serving.pool import UNMATERIALIZED

        with obs_trace.span("serve.grow"):
            for i in list(live):
                req = self.active[i]
                if req is None:
                    continue  # already evicted by an earlier row's make_room
                need = self._write_need(req.rid, int(self.positions[i]))
                if need and self.store.n_free < need:
                    if not self._make_room(need, req.rid, strict=False):
                        self._preempt(req.rid)
                        continue
                pos = int(self.positions[i])
                if need and self._dev_views is not None:
                    # materialisation / COW split: bookkeeping on the host,
                    # the page's payload patched on the device by id
                    dst, origin = self.store.claim_write(req.rid, pos)
                    if origin == UNMATERIALIZED:
                        self._pending_fills.append(int(dst))
                    elif dst != origin:  # COW split: clone the shared page
                        self._pending_copies.append((int(origin), int(dst)))
                else:
                    self.store.prepare_write(req.rid, pos)
        live = [i for i, r in enumerate(self.active) if r is not None]
        if not live:
            return 0
        # device tables: unmaterialised slots (and dead rows) target the
        # scratch page appended past the pool — always masked by lengths.
        # The table is sized to the batch's live high-water mark (grown
        # monotonically so the fused step recompiles at most once per
        # growth step, never thrashes): paged attention then reads ONLY
        # pages any request can occupy, instead of paying the full
        # cache_len width the dense rows are stuck with.
        with obs_trace.span("serve.tables"):
            P = self.store.state.n_pages
            T = self.layout.page_tokens
            need = max(int(self.positions[i]) // T + 1 for i in live)
            need = min(self.layout.n_pages, -(-need // 4) * 4)  # 4-page buckets
            self._table_width = max(self._table_width, need)
            tables = np.full((self.B, self._table_width), P, np.int32)
            for i in live:
                row = self.store.device_table(self.active[i].rid, absent=P)
                tables[i] = row[: self._table_width]
        m = self.metrics
        m.counter("ticks").inc()
        m.counter("decode_rows").inc(len(live))
        peak = m.gauge("pool_pages_peak")
        peak.set(max(peak.get(), P - self.store.n_free))
        logits = self._decode_via_tables(tables)
        with obs_trace.span("serve.advance"):
            for i in live:
                if i not in self.replaying:  # replays are not new generation
                    self.scheduler.on_step(self.active[i].rid)
            self._advance(live, logits)
        return len(live)

    def _after_step(self) -> None:
        if self.health is not None:
            self._tick_no += 1
            self.health.tick(
                self._tick_no, time.monotonic(),
                progress={
                    r.rid: len(r.out)
                    for r in self.active if r is not None
                },
            )

    def _ensure_dev_views(self) -> None:
        """(Re-)upload the host mirror when it is authoritative: absent
        pages on the device, then the live ones in page chunks — the
        pool never crosses as one float32 carrier."""
        if self._dev_views is not None:
            return
        with obs_trace.span("serve.upload"):
            jnp = self.jnp
            P = self.store.state.n_pages
            row = self.layout.empty_page_row()[None]
            empty = self.layout.decode_views(jnp.asarray(row))
            h2d = self.metrics.counter("h2d_bytes")
            h2d.inc(row.nbytes)
            self._dev_views = self.jax.tree_util.tree_map(
                lambda e: jnp.broadcast_to(
                    e, e.shape[:1] + (P + 1,) + e.shape[2:]
                ),
                empty,
            )
            live = self._live_pages()
            for lo in range(0, len(live), _PATCH_CHUNK):
                dst = np.full((_PATCH_CHUNK,), P, np.int32)
                chunk = live[lo : lo + _PATCH_CHUNK]
                dst[: len(chunk)] = chunk
                rows = np.zeros((_PATCH_CHUNK, row.shape[1]), np.float32)
                rows[: len(chunk)] = self.store.mem[chunk]
                self._dev_views = self._upload(
                    self._dev_views, jnp.asarray(dst), jnp.asarray(rows))
                h2d.inc(dst.nbytes + rows.nbytes)

    def _decode_via_tables(self, tables: np.ndarray) -> np.ndarray:
        """Upload the pool when host-resident, flush queued page patches,
        run the fused paged decode; returns host logits.  The device-pool
        representation is this method's private affair — the TP variant
        swaps in a head-sharded stacked carrier and a ``shard_map``-ped
        step without touching the scheduler loop above."""
        self._ensure_dev_views()
        if self._has_pending():
            self._apply_pending()
        with obs_trace.span("serve.decode"):
            logits, self._dev_views = self._decode_paged(
                self.params,
                self.jnp.asarray(self.last_token),
                self.jnp.asarray(self.positions),
                self._dev_views,
                self.jnp.asarray(tables),
            )
        with obs_trace.span("serve.logits"):
            return np.asarray(logits)

    # ------------------------------------------------------------------ #
    def _post_decode(self, live: List[int], written: Dict[int, int]) -> None:
        """Dense-path (``paged_decode=False``) per-step writeback: the
        page holding the position this step wrote goes back into the pool
        (copy-on-write split if still shared), keeping the pool canonical
        — only that one page is flattened.  The paged-decode path writes
        on device and never comes through here."""
        if self.paged_decode:
            return
        for i in live:
            req = self.active[i]
            row = self.jax.tree.map(lambda x: x[:, i : i + 1], self.caches)
            pos = written[i]
            page_row = self.layout.flatten_page(
                row, pos // self.layout.page_tokens
            )
            self.store.write_token_page(req.rid, pos, np.asarray(page_row))

    def _release(self, req: Request) -> None:
        self.store.release(req.rid)
        if req.rid in self._by_rid:
            self.scheduler.on_done(req.rid)
        if self.health is not None:
            self.health.retire(req.rid)

    def run_until_drained(self, max_ticks: int = 10000) -> Dict[str, Any]:
        stats = super().run_until_drained(max_ticks)
        self._sync_host()  # callers may inspect the pool post-drain
        stats.update({f"pool_{k}": v for k, v in self.store.stats().items()})
        stats.update(self.tier.stats())
        stats.update(self.scheduler.stats())
        return stats


def _tp_paged_decode_fn(model, ctx, shard_layout, tp: int, backend,
                        mesh, costs=None):
    """The tensor-parallel fused paged decode step: a ``shard_map`` over a
    ``("tp",)`` mesh where every rank holds one head shard of the weights
    and one head shard of the page pool, and each sub-block's partial sum
    crosses the group through ``sched.all_reduce`` — the planned,
    engine-aware collective (``backend`` may be a mixed spec like
    ``"xla,gascore"``, planning against the worst member edge).  Logits
    are replicated across the group (bit-identically: the 2-rank ring and
    recursive-doubling schedules commute)."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.core import sched as core_sched
    from repro.core.engine import make_engine
    from repro.parallel.tp import TPGroup

    empty_row = np.asarray(shard_layout.empty_page_row())

    def body(params, token, positions, mem, tables):
        params = jax.tree_util.tree_map(lambda x: x[0], params)
        mem = mem[0]
        mem = mem.at[mem.shape[0] - 1].set(jnp.asarray(empty_row, mem.dtype))
        engine = make_engine(backend, "tp", tp)
        group = TPGroup(
            tp, lambda x: core_sched.all_reduce(engine, x, costs=costs)
        )
        views = shard_layout.decode_views(mem)
        logits, views = model.decode_step_paged(
            params, ctx, token, positions, views, tables, tp=group
        )
        return logits, shard_layout.views_to_pool(views)[None]

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P("tp"), P(), P(), P("tp"), P()),
        out_specs=(P(), P("tp")),
        check_vma=False,
    )
    return jax.jit(fn, donate_argnums=(3,))


def _tp_pool_patch_fn():
    """Stacked-carrier variant of :func:`_pool_patch_fn`: the pool is the
    raw ``(tp, P+1, shard_page_elems)`` carrier (one head shard per
    rank), rows arrive pre-sharded ``(tp, chunk, shard_page_elems)``."""
    import jax

    @functools.partial(jax.jit, donate_argnums=(0,))
    def patch(pool, write_dst, rows, copy_src, copy_dst):
        pool = pool.at[:, write_dst].set(rows)
        pool = pool.at[:, copy_dst].set(pool[:, copy_src])
        return pool

    return patch


class TPPagedServer(PagedServer):
    """:class:`PagedServer` whose decode runs over a tensor-parallel group
    of ``tp`` GAS ranks: attention heads and MLP columns sharded per rank
    (``repro.parallel.tp``), each rank's device pool holding only its
    heads' slice of every page (``PagedLayout.shard_heads``), one planned
    all-reduce per sub-block inside the tick program.

    Everything host-side is unchanged from the base class: the allocator,
    page tables, prefix index, scheduler, tier, and the host ``mem``
    mirror all stay in the FULL layout (pages are sharded by *bytes*, not
    by id — every rank holds the same table).  Only the device residency
    differs: ``_dev_views`` becomes the stacked ``(tp, P+1, shard_elems)``
    carrier, and ``_sync_host`` reassembles the shards bit-exactly.
    Admission takes the host route: the prefill's cache is flattened to
    the host, written into the mirror, and its fresh pages go up as
    payload rows pre-sharded through ``shard_cols`` (with lazy-growth
    pages, as absent rows).  Token streams are identical to ``tp=1``
    (asserted in tests and the bench section).
    """

    def __init__(self, model, ctx, params, batch_size: int, cache_len: int,
                 tp: int = 2, tp_backend: str = "xla",
                 sched_cost_table: Optional[Dict[str, Any]] = None, **kw):
        super().__init__(model, ctx, params, batch_size, cache_len, **kw)
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from repro.launch.mesh import make_mesh
        from repro.parallel import tp as tp_lib

        tp_lib.validate_tp(model.cfg, tp)
        if jax.device_count() < tp:
            raise ValueError(
                f"tp={tp} needs {tp} devices, have {jax.device_count()}"
            )
        self.tp = tp
        self.shard_layout, self.shard_cols = self.layout.shard_heads(
            tp, model.cfg.n_kv_heads
        )
        self._tp_mesh = make_mesh((tp,), ("tp",))
        self._tp_sharding = NamedSharding(self._tp_mesh, P("tp"))
        self._stacked_params = jax.device_put(
            tp_lib.stack_shards(params, tp), self._tp_sharding
        )
        self._decode_tp = _tp_paged_decode_fn(
            model, ctx, self.shard_layout, tp, tp_backend, self._tp_mesh,
            costs=sched_cost_table,
        )
        self._patch_tp = _tp_pool_patch_fn()
        # fresh payload rows (admissions) queued for the stacked carrier
        self._pending_rows: Dict[int, np.ndarray] = {}

    # -- device-pool representation overrides --------------------------- #
    def _admit_prompt(self, req: Request) -> int:
        """The host route: flatten the prefill's cache to the host, write
        its fresh pages into the mirror and queue them as payload rows."""
        with obs_trace.span("serve.admit"):
            tok, caches = self._prefill_pages(req)
            with obs_trace.span("serve.flatten"):
                pages = np.asarray(self.layout.flatten(caches))
            self.metrics.counter("d2h_bytes").inc(pages.nbytes)
            with obs_trace.span("serve.pool_write"):
                plan = self.store.plan_admit(req.prompt, lazy=True)
                self.store.write_pages(plan, pages)
                self.store.commit(req.rid, plan)
                if self._dev_views is not None:
                    for page_id, is_fresh in zip(plan.table, plan.fresh):
                        if is_fresh:
                            self._pending_rows[page_id] = self.store.mem[
                                page_id
                            ].copy()
        self._count_admission(req, plan, 0)
        return tok

    def _has_pending(self) -> bool:
        return bool(self._pending_rows) or super()._has_pending()

    def _apply_pending(self) -> None:
        jnp = self.jnp
        Pn = self.store.state.n_pages  # scratch index pads the chunks
        elems = self.shard_layout.page_elems
        empty = self.layout.empty_page_row()
        rows = list(self._pending_rows.items())
        rows += [(pg, empty) for pg in self._pending_fills]
        copies = list(self._pending_copies)
        self._pending_rows.clear()
        self._pending_fills.clear()
        self._pending_copies.clear()
        pad_idx = np.full((_PATCH_CHUNK,), Pn, np.int32)
        pad_rows = np.zeros((self.tp, _PATCH_CHUNK, elems), np.float32)
        while rows:
            chunk, rows = rows[:_PATCH_CHUNK], rows[_PATCH_CHUNK:]
            wd, wr = pad_idx.copy(), pad_rows.copy()
            for j, (pg, row) in enumerate(chunk):
                wd[j] = pg
                wr[:, j] = row[self.shard_cols]  # pre-shard the full row
            self._dev_views = self._patch_tp(
                self._dev_views, jnp.asarray(wd), jnp.asarray(wr),
                jnp.asarray(pad_idx), jnp.asarray(pad_idx),
            )
        while copies:
            chunk, copies = copies[:_PATCH_CHUNK], copies[_PATCH_CHUNK:]
            cs, cd = pad_idx.copy(), pad_idx.copy()
            for j, (src, dst) in enumerate(chunk):
                cs[j], cd[j] = src, dst
            self._dev_views = self._patch_tp(
                self._dev_views, pad_idx, pad_rows,
                jnp.asarray(cs), jnp.asarray(cd),
            )

    def _sync_host(self) -> None:
        if self._dev_views is None:
            return
        if self._has_pending():
            self._apply_pending()
        Pn = self.store.state.n_pages
        stacked = np.asarray(self._dev_views)  # (tp, P+1, shard_elems)
        full = np.empty((Pn, self.layout.page_elems), np.float32)
        for s in range(self.tp):
            full[:, self.shard_cols[s]] = stacked[s, :Pn]
        self.store.mem[:] = full
        self._dev_views = None

    def _decode_via_tables(self, tables: np.ndarray) -> np.ndarray:
        jnp = self.jnp
        if self._dev_views is None:  # upload, pre-sharded per rank
            mem_cat = np.concatenate(
                [self.store.mem, self.layout.empty_page_row()[None]], axis=0
            )
            stacked = np.stack([mem_cat[:, c] for c in self.shard_cols])
            self._dev_views = self.jax.device_put(
                jnp.asarray(stacked), self._tp_sharding
            )
        if self._has_pending():
            self._apply_pending()
        logits, self._dev_views = self._decode_tp(
            self._stacked_params,
            jnp.asarray(self.last_token),
            jnp.asarray(self.positions),
            self._dev_views,
            jnp.asarray(tables),
        )
        return np.asarray(logits)


class PooledDecodeServer(Server):
    """Decode server whose KV lives in an EXTERNAL paged store — the
    disaggregated cluster's per-rank pool shard.

    Rows are bound to page tables by rid (:meth:`admit_paged`); no dense
    cache row is ever built, and every tick decodes through
    ``Model.decode_step_paged`` — the same single decode path the
    colocated :class:`PagedServer` runs, so dense ``decode_step`` survives
    only as the test oracle.

    Division of labour with the cluster:

    - the cluster owns prefill, admission (page puts over the GAS layer),
      preemption policy, release, and resume;
    - the server owns the per-tick write-page claim
      (``store.prepare_write``) and the batched paged decode;
    - :meth:`drain_dirty` exposes the physical pages each tick wrote so
      the cluster can replay them onto a freshly *consumed* pool segment
      — the decode step overlaps an in-flight transfer program whose
      result replaces the whole segment the store's mirror aliases.

    When the pool shard runs dry mid-growth (tiered clusters
    oversubscribe), ``on_page_shortage(rid, need)`` asks the cluster to
    preempt; if pages still aren't free the row *stalls* one tick: its
    write slot is remapped to the scratch page (so a pending
    copy-on-write split can't corrupt sharers) and its logits are
    discarded — it retries once the swap-out lands.
    """

    def __init__(self, model, ctx, params, batch_size: int, cache_len: int,
                 store, eos_id: int = -1, greedy: bool = True, seed: int = 0,
                 on_page_shortage=None):
        super().__init__(model, ctx, params, batch_size, cache_len,
                         eos_id=eos_id, greedy=greedy, seed=seed)
        self.store = store
        self.layout = store.layout
        self.on_page_shortage = on_page_shortage
        self.paged_decode_steps = 0
        self._dirty: Dict[int, np.ndarray] = {}
        self._decode_paged = _paged_decode_fn(model, ctx, self.layout)

    def _admit(self) -> None:
        """Admission belongs to the cluster (prefill nodes + GAS puts)."""

    def admit_paged(
        self, req: Request, first_token: int, position: int
    ) -> bool:
        """Bind an installed request's decode row to its page table: the
        pool shard — not any dense copy — is the KV source of truth.
        Returns False when no decode row is free."""
        slot = self._free_slot()
        if slot is None:
            return False
        if not req.out:
            req.out.append(int(first_token))
        tr = obs_trace.active()
        if not req.t_first:
            req.t_first = time.monotonic()
            if tr.enabled:
                tr.instant("req_first_token", cat="req",
                           rank=self.trace_rank, rid=req.rid)
        if tr.enabled:
            tr.instant("req_admit", cat="req", rank=self.trace_rank,
                       rid=req.rid, slot=slot, position=position)
        self.active[slot] = req
        self.positions[slot] = position
        self.last_token[slot, 0] = int(first_token)
        return True

    def drain_dirty(self) -> Dict[int, np.ndarray]:
        """Physical page -> row payload written since the last drain."""
        d = self._dirty
        self._dirty = {}
        return d

    def _step(self) -> int:
        live = [i for i, r in enumerate(self.active) if r is not None]
        if not live:
            return 0
        jnp = self.jnp
        # row -> the physical page this tick's write lands in; rows absent
        # here at decode time are stalled (no write page) and discarded
        written: Dict[int, int] = {}
        for i in list(live):
            req = self.active[i]
            if req is None:
                continue  # evicted by an earlier row's shortage handling
            pos = int(self.positions[i])
            need = _pool_write_need(self.store, self.layout, req.rid, pos)
            if need and self.store.n_free < need:
                ok = bool(self.on_page_shortage) and self.on_page_shortage(
                    req.rid, need
                )
                if self.active[i] is None:
                    continue  # the shortage handler preempted this row
                if not ok:
                    continue  # stall: retry once freed pages land
            written[i] = self.store.prepare_write(req.rid, pos)
        live = [i for i, r in enumerate(self.active) if r is not None]
        if not live:
            return 0
        P = self.store.state.n_pages
        tables = np.full((self.B, self.layout.n_pages), P, np.int32)
        for i in live:
            tables[i] = self.store.device_table(self.active[i].rid, absent=P)
            if i not in written:
                # stalled: scatter into scratch, never a shared page
                slot = int(self.positions[i]) // self.layout.page_tokens
                tables[i, slot] = P
        logits = self._decode_and_write(written, tables)
        advanced = [i for i in live if i in written]
        self._advance(advanced, logits)
        return len(advanced)

    def _decode_and_write(self, written: Dict[int, int],
                          tables: np.ndarray) -> np.ndarray:
        """Run the fused paged decode over the pool mirror and land this
        tick's written pages back in it (plus the dirty set the cluster
        replays after a transfer consume)."""
        jnp = self.jnp
        mem = np.concatenate(
            [self.store.mem, self.layout.empty_page_row()[None]], axis=0
        )
        logits, newmem = self._decode_paged(
            self.params,
            jnp.asarray(self.last_token),
            jnp.asarray(self.positions),
            jnp.asarray(mem),
            jnp.asarray(tables),
        )
        self.paged_decode_steps += 1
        # download ONLY the pages this tick wrote — the rest of the shard
        # is bit-identical to the mirror the transfer program already read
        pages = sorted(set(written.values()))
        if pages:
            rows = np.asarray(newmem[np.asarray(pages, np.int32)])
            for pp, row in zip(pages, rows):
                self.store.mem[pp] = row
                self._dirty[pp] = row
        return np.asarray(logits)


class TPPooledDecodeServer(PooledDecodeServer):
    """One logical decode server for a tensor-parallel GROUP of cluster
    ranks: the group's page-pool shard is striped across the members'
    GASNet segments BY HEADS (member ``s`` holds every page's slice for
    its heads — ``PagedLayout.shard_heads``), and each tick's decode runs
    as a ``shard_map`` over the group's devices with one planned
    all-reduce per sub-block (:func:`_tp_paged_decode_fn`).

    The allocator, page tables, and request rows are group-level (one
    logical server, one store); only page *payloads* are sharded.  The
    cluster aliases ``shard_mems`` — a live, re-bound-per-consume list of
    the members' pool-partition mirrors (entry 0 is ``store.mem``, the
    leader's) — and ``drain_dirty`` hands back stacked ``(tp, elems)``
    rows so the replay lands on every member mirror."""

    def __init__(self, model, ctx, params, batch_size: int, cache_len: int,
                 store, shard_mems: List[np.ndarray], tp: int,
                 tp_backend: str = "xla", tp_mesh=None,
                 costs: Optional[Dict[str, Any]] = None, eos_id: int = -1,
                 greedy: bool = True, seed: int = 0, on_page_shortage=None):
        super().__init__(model, ctx, params, batch_size, cache_len,
                         store=store, eos_id=eos_id, greedy=greedy,
                         seed=seed, on_page_shortage=on_page_shortage)
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from repro.launch.mesh import make_mesh
        from repro.parallel import tp as tp_lib

        tp_lib.validate_tp(model.cfg, tp)
        self.tp = tp
        self.shard_mems = shard_mems  # cluster-owned, re-aliased in place
        if tp_mesh is None:
            tp_mesh = make_mesh((tp,), ("tp",))
        self._tp_sharding = NamedSharding(tp_mesh, P("tp"))
        self._stacked_params = jax.device_put(
            tp_lib.stack_shards(params, tp), self._tp_sharding
        )
        # self.layout is the SHARD layout (the store is built with it)
        self._decode_tp = _tp_paged_decode_fn(
            model, ctx, self.layout, tp, tp_backend, tp_mesh, costs=costs
        )

    def _decode_and_write(self, written: Dict[int, int],
                          tables: np.ndarray) -> np.ndarray:
        jnp = self.jnp
        empty = np.asarray(self.layout.empty_page_row())
        mem = np.stack([
            np.concatenate([sm, empty[None]], axis=0)
            for sm in self.shard_mems
        ])
        logits, newmem = self._decode_tp(
            self._stacked_params,
            jnp.asarray(self.last_token),
            jnp.asarray(self.positions),
            jnp.asarray(mem),
            jnp.asarray(tables),
        )
        self.paged_decode_steps += 1
        pages = sorted(set(written.values()))
        if pages:
            rows = np.asarray(newmem[:, np.asarray(pages, np.int32)])
            for j, pp in enumerate(pages):
                for s in range(self.tp):
                    self.shard_mems[s][pp] = rows[s, j]
                self._dirty[pp] = rows[:, j].copy()
        return np.asarray(logits)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--smoke", action="store_true",
                    help="the architecture's small test preset instead of "
                         "its published widths")
    ap.add_argument("--role", choices=("prefill", "decode", "memory", "both"),
                    default="both",
                    help="both = disaggregated cluster (prefill pool + "
                         "decode pool + optional memory ranks over the "
                         "GAS layer); decode = colocated continuous "
                         "batching; prefill = prefill pool alone; memory "
                         "= a memory-only GAS rank (segment capacity, no "
                         "model compute — reports its tier geometry)")
    ap.add_argument("--n-prefill", type=int, default=1)
    ap.add_argument("--n-decode", type=int, default=1)
    ap.add_argument("--n-memory", type=int, default=0,
                    help="memory-only ranks joining the paged cluster: "
                         "their segments hold the swap tier "
                         "(repro.serving.tier)")
    ap.add_argument("--prefill-backend", default="xla",
                    help="engine of the prefill pool (xla|gascore)")
    ap.add_argument("--decode-backend", default="xla",
                    help="engine of the decode pool (xla|gascore)")
    ap.add_argument("--memory-backend", default="xla",
                    help="engine of the memory ranks (xla|gascore)")
    ap.add_argument("--mem-slots", type=int, default=None,
                    help="tier page slots per memory rank")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--cache-len", type=int, default=64)
    ap.add_argument("--paged", action="store_true",
                    help="KV lives in the global paged pool "
                         "(repro.serving.pool): pages allocated/freed per "
                         "request, prompt prefixes shared by page table")
    ap.add_argument("--page-tokens", type=int, default=8,
                    help="tokens per KV page (must divide --cache-len)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel group size: decode shards "
                         "attention heads / MLP columns over tp GAS ranks "
                         "with a planned all-reduce per sub-block "
                         "(requires --paged; with --role both, tp must "
                         "divide --n-decode)")
    ap.add_argument("--tp-backend", default="xla",
                    help="engine spec of the TP group's all-reduce — a "
                         "single backend or a mixed per-rank list "
                         "(e.g. 'xla,gascore')")
    args = ap.parse_args()

    import jax

    from repro.configs.registry import ARCHS, SMOKE
    from repro.launch import compile_cache
    from repro.models.build import build_model
    from repro.parallel.ctx import RunCtx

    compile_cache.enable()
    dev = jax.devices()[0]
    print(f"platform: {dev.platform} device_kind: {dev.device_kind} "
          f"devices: {jax.device_count()}")
    cfg = (SMOKE if args.smoke else ARCHS)[args.arch]
    model = build_model(cfg)
    ctx = RunCtx(mesh=None, remat="none")
    params = jax.jit(lambda k: model.init(ctx, k)[0])(jax.random.PRNGKey(0))

    rng = np.random.default_rng(0)
    reqs = [
        Request(
            rid=rid,
            prompt=rng.integers(0, cfg.vocab, size=args.prompt_len).tolist(),
            max_new=args.max_new,
        )
        for rid in range(args.requests)
    ]

    if args.role == "decode":
        if args.paged and args.tp > 1:
            server = TPPagedServer(model, ctx, params, args.batch,
                                   args.cache_len,
                                   page_tokens=args.page_tokens,
                                   tp=args.tp, tp_backend=args.tp_backend)
        elif args.paged:
            server = PagedServer(model, ctx, params, args.batch,
                                 args.cache_len, page_tokens=args.page_tokens)
        else:
            if args.tp > 1:
                raise SystemExit("--tp > 1 requires --paged")
            server = Server(model, ctx, params, args.batch, args.cache_len)
        for req in reqs:
            server.submit(req)
        stats = server.run_until_drained()
    elif args.role == "memory":
        # a memory-only GAS rank: it exports segment capacity into the
        # global address space and runs no model compute — report the
        # tier geometry it would contribute to a paged cluster.
        from repro.serving.pool import PagedLayout
        from repro.serving.tier import MemoryTier

        layout = PagedLayout.from_struct(
            model.kv_block_struct(
                ctx, prompt_len=4, cache_len=args.cache_len
            ),
            cache_len=args.cache_len, page_tokens=args.page_tokens,
        )
        slots = args.mem_slots or 2 * args.batch * layout.n_pages
        tier = MemoryTier(1, slots, layout.page_elems)
        stats = dict(tier.stats())
        stats.update({
            "role": "memory",
            "page_bytes": layout.page_bytes,
            "segment_bytes": slots * layout.page_bytes,
        })
    elif args.role == "prefill":
        prefill = jax.jit(
            lambda p, b: model.prefill(p, ctx, b, cache_len=args.cache_len)
        )
        import jax.numpy as jnp

        t0 = time.monotonic()
        for req in reqs:
            logits, _ = prefill(
                params, {"inputs": jnp.asarray(req.prompt, jnp.int32)[None]}
            )
            jax.block_until_ready(logits)
        dt = time.monotonic() - t0
        stats = {
            "requests": len(reqs),
            "wall_s": dt,
            "kv_blocks_per_s": len(reqs) / dt if dt else 0.0,
        }
    else:
        from repro.serving.disagg import DisaggCluster

        cluster = DisaggCluster(
            model, ctx, params,
            n_prefill=args.n_prefill, n_decode=args.n_decode,
            n_memory=args.n_memory,
            decode_batch=args.batch, cache_len=args.cache_len,
            prefill_backend=args.prefill_backend,
            decode_backend=args.decode_backend,
            memory_backend=args.memory_backend,
            paged=args.paged or args.n_memory > 0 or args.tp > 1,
            page_tokens=args.page_tokens,
            mem_slots_per_rank=args.mem_slots,
            tp=args.tp, tp_backend=args.tp_backend,
        )
        for req in reqs:
            cluster.submit(req)
        stats = cluster.run_until_drained()

    for k, v in stats.items():
        print(f"{k}: {v}")


if __name__ == "__main__":
    main()
