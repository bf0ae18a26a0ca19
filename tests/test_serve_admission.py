"""The colocated paged server's admission path: prompt pages go from the
prefill's cache into the device pool on the device, lazy growth and
copy-on-write splits patch the pool by page id, and the host mirror
(``PagedKVStore.mem``) holds payloads only between a host sync and the
next upload.

Token streams are compared with the dense :class:`Server`; the pool read
back by a host sync is compared, bit for bit, with the pool of a server
that keeps every payload write on the host mirror, as the paged server
did before admission moved onto the device."""
import jax
import numpy as np
import pytest

from repro.launch.serve import PagedServer, Request, Server
from repro.serving import pool

CACHE_LEN, PAGE_TOKENS, BATCH = 32, 8, 2


@pytest.fixture(scope="module")
def smoke():
    from repro.configs.registry import SMOKE
    from repro.models.build import build_model
    from repro.parallel.ctx import RunCtx

    cfg = SMOKE["qwen3-4b"]
    model = build_model(cfg)
    ctx = RunCtx(mesh=None, remat="none")
    params, _ = model.init(ctx, jax.random.PRNGKey(0))
    return cfg, model, ctx, params


def _prompts(cfg):
    """Two prompts that share a two-page prefix (one of them exactly two
    pages long, so its first decode materialises a page), and one more."""
    rng = np.random.default_rng(11)
    shared = rng.integers(0, cfg.vocab, 2 * PAGE_TOKENS).tolist()
    return {
        0: shared + [3],
        1: list(shared),
        2: rng.integers(0, cfg.vocab, 7).tolist(),
    }


def _dense_tokens(smoke, prompts, max_new):
    cfg, model, ctx, params = smoke
    dense = Server(model, ctx, params, BATCH, CACHE_LEN)
    for rid, p in prompts.items():
        dense.submit(Request(rid=rid, prompt=list(p), max_new=max_new))
    dense.run_until_drained()
    return {r.rid: r.out for r in dense.finished}


def _paged(smoke, cls=PagedServer, **kw):
    cfg, model, ctx, params = smoke
    return cls(model, ctx, params, BATCH, CACHE_LEN,
               page_tokens=PAGE_TOKENS, **kw)


def _slot_rows(layout, row, slots):
    """The carrier elements of token ``slots`` of one page row."""
    out = []
    for leaf in layout.leaves:
        col = row[leaf.offset : leaf.offset + leaf.size]
        out.append(col.reshape(layout.page_tokens, -1)[slots])
    return np.concatenate(out, axis=1)


def test_paged_server_matches_dense_across_sharing_growth_and_cow(smoke):
    cfg = smoke[0]
    prompts = _prompts(cfg)
    want = _dense_tokens(smoke, prompts, max_new=10)

    srv = _paged(smoke)
    for rid, p in prompts.items():
        srv.submit(Request(rid=rid, prompt=list(p), max_new=10))
    srv.step()  # admits rid 0 and 1 (prefix shared), decodes position 17/16
    assert srv.store.prefix_hits >= 2
    # rid 0 writes position 18 next, in its private page 2: pin that page
    # so the write copy-on-write splits it (on the device, by id)
    before = srv.store.tables[0][2]
    srv.store.pin_pages([before])
    srv.step()
    after = srv.store.tables[0][2]
    assert after != before
    m = srv.metrics.snapshot()
    assert m["pages_filled_on_device"] >= 1  # rid 1 grew page 2 lazily
    # nothing of a payload was written on the host while the pool lived
    # on the device
    assert not srv.store.mem.any()
    assert m["d2h_bytes"] == 0 and m["host_syncs"] == 0
    srv._sync_host()
    orig, clone = srv.store.mem[before], srv.store.mem[after]
    # the split page starts as the shared page: positions 16 and 17
    # copied, 18 written by the decode after the copy; the pinned
    # original kept its bytes (position 18 still absent there)
    np.testing.assert_array_equal(
        _slot_rows(srv.layout, clone, [0, 1]),
        _slot_rows(srv.layout, orig, [0, 1]))
    empty = srv.layout.empty_page_row()
    np.testing.assert_array_equal(
        _slot_rows(srv.layout, orig, [2]), _slot_rows(srv.layout, empty, [2]))
    assert (_slot_rows(srv.layout, clone, [2])
            != _slot_rows(srv.layout, empty, [2])).any()
    srv.store.unpin_pages()
    stats = srv.run_until_drained()
    got = {r.rid: r.out for r in srv.finished}
    assert got == want
    assert stats["pool_n_free"] == stats["pool_n_pages"]
    pool.check_pool(srv.store.state)


@pytest.mark.parametrize("mode", ["swap", "recompute"])
def test_forced_preempt_and_resume_stay_bit_identical(smoke, mode):
    cfg = smoke[0]
    prompts = _prompts(cfg)
    want = _dense_tokens(smoke, prompts, max_new=12)

    srv = _paged(smoke)
    for rid, p in prompts.items():
        srv.submit(Request(rid=rid, prompt=list(p), max_new=12))
    for _ in range(4):
        srv.step()
    srv._preempt(0, mode)
    assert srv.metrics.counter("host_syncs").get() == 1
    for _ in range(3):
        srv.step()
    srv._preempt(1, mode)  # a second preemption after re-upload
    stats = srv.run_until_drained(max_ticks=300)
    got = {r.rid: r.out for r in srv.finished}
    assert got == want
    assert stats["sched_swaps" if mode == "swap" else
                 "sched_recomputes"] >= 2
    assert stats["pool_n_free"] == stats["pool_n_pages"]
    assert stats["tier_free_slots"] == stats["tier_slots"]


class HostMirrorServer(PagedServer):
    """Every payload write on the host mirror: an admission flattens the
    prefill's cache to the host and writes its fresh pages there, growth
    writes absent pages there, and the pool goes up again before each
    decode and comes back after it."""

    def _admit_prompt(self, req):
        tok, caches = self._prefill_pages(req)
        pages = np.asarray(self.layout.flatten(caches))
        plan = self.store.plan_admit(req.prompt, lazy=True)
        self.store.write_pages(plan, pages)
        self.store.commit(req.rid, plan)
        return tok

    def _decode_via_tables(self, tables):
        logits = super()._decode_via_tables(tables)
        self._sync_host()
        return logits


def test_synced_pool_equals_the_host_mirror_pool(smoke):
    cfg = smoke[0]
    prompts = _prompts(cfg)
    dev, host = _paged(smoke), _paged(smoke, HostMirrorServer)
    for srv in (dev, host):
        for rid, p in prompts.items():
            srv.submit(Request(rid=rid, prompt=list(p), max_new=12))
    for ticks in (3, 4):
        for srv in (dev, host):
            for _ in range(ticks):
                srv.step()
            srv._sync_host()
        assert dev.store.tables == host.store.tables
        live = dev._live_pages()
        assert len(live) and np.array_equal(live, host._live_pages())
        assert dev.store.mem[live].tobytes() == host.store.mem[live].tobytes()
    dev.run_until_drained()
    host.run_until_drained()
    assert {r.rid: r.out for r in dev.finished} == {
        r.rid: r.out for r in host.finished}


def test_admission_moves_page_ids_only(smoke):
    cfg = smoke[0]
    prompts = _prompts(cfg)
    srv = _paged(smoke)
    plans = []
    admit_tables = srv.store.admit_tables

    def spy(*a, **k):
        plans.append(admit_tables(*a, **k))
        return plans[-1]

    srv.store.admit_tables = spy
    srv.submit(Request(rid=0, prompt=list(prompts[0]), max_new=4))
    srv.step()  # the first admission uploads the (empty) pool
    for rid in (1, 2):
        srv.submit(Request(rid=rid, prompt=list(prompts[rid]), max_new=4))
    before = srv.metrics.snapshot()
    req = srv._by_rid[1]
    srv.queue.remove(req)
    srv._admit_prompt(req)
    after = srv.metrics.snapshot()
    d = {k: after[k] - before[k] for k in after}
    fresh = sum(plans[-1].fresh)
    assert plans[-1].shared and fresh == 0  # rid 1's prompt is all shared
    assert d["pages_written_on_device"] == 0 and d["h2d_bytes"] == 0
    req = srv._by_rid[2]
    srv.queue.remove(req)
    srv._admit_prompt(req)
    after2 = srv.metrics.snapshot()
    d = {k: after2[k] - after[k] for k in after}
    fresh = sum(plans[-1].fresh)
    assert fresh == srv.layout.pages_for(len(prompts[2])) > 0
    assert d["pages_written_on_device"] == fresh
    assert d["d2h_bytes"] == 0
    # the source and destination page ids, n_pages wide, int32
    assert d["h2d_bytes"] == 2 * srv.layout.n_pages * 4
    assert d["admissions"] == 1 and d["host_syncs"] == 0
