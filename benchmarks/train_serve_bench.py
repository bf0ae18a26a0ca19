"""End-to-end benchmarks: train-step throughput + decode tokens/s +
disaggregated serving (smoke configs).

Prints ``name,us_per_call,derived`` CSV rows; with ``--json PATH``
(default ``BENCH_serve.json`` when the flag is given bare) it also writes
a machine-readable artifact: serve throughput, p50/p99 request latency,
TTFT, and the KV-transfer goodput (bytes/sec) of the disaggregated
cluster — the serving-side numbers CI tracks next to ``BENCH_gas``.

The disaggregated section needs several devices; on the CPU run it with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``.
"""
import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from _report import append_history, make_report, new_result, write_artifact

from repro.launch.mesh import make_mesh

RESULT = new_result()
report = make_report(RESULT)

SECTIONS = ("train", "serve", "disagg", "paged", "oversub", "tp", "obs")


def merge_artifact(result: dict, path: str) -> None:
    """Write ``result``'s rows into an existing artifact, replacing rows
    of the same name and keeping the rest — how a single-section run
    (``--sections tp``) refreshes its slice of ``BENCH_serve.json``
    without discarding the other sections' measurements."""
    try:
        with open(path) as f:
            artifact = json.load(f)
    except (OSError, ValueError):
        artifact = new_result()
    fresh = {r["name"] for r in result["rows"]}
    artifact["rows"] = [
        r for r in artifact.get("rows", []) if r.get("name") not in fresh
    ] + result["rows"]
    write_artifact(artifact, path)


def main(json_path: str | None = None,
         sections: tuple | None = None) -> None:
    from repro.configs.registry import SMOKE
    from repro.data.synthetic import SyntheticLM
    from repro.models.build import build_model
    from repro.optim import adamw
    from repro.parallel.ctx import RunCtx
    from repro.runtime.trainer import Trainer, TrainerConfig

    ctx = RunCtx(mesh=None, remat="none")

    def want(s: str) -> bool:
        return sections is None or s in sections

    for arch in () if not want("train") else (
            "qwen3-4b", "falcon-mamba-7b", "arctic-480b"):
        cfg = SMOKE[arch]
        model = build_model(cfg)
        tr = Trainer(model, ctx, adamw.AdamWConfig(lr=1e-3),
                     TrainerConfig(steps=1, ckpt_every=0))
        params, st = tr.init(jax.random.PRNGKey(0))
        fn = tr.make_train_step()
        B, S = 8, 128
        src = SyntheticLM(cfg, batch=B, seq_len=S, seed=0)
        batch = {k: jnp.asarray(v) for k, v in src.batch_at(0).items()}
        params, st, _ = fn(params, st, batch)  # compile+warm
        iters = 5
        t0 = time.perf_counter()
        for _ in range(iters):
            params, st, m = fn(params, st, batch)
        jax.block_until_ready(m["loss"])
        us = (time.perf_counter() - t0) / iters * 1e6
        tok_s = B * S / (us * 1e-6)
        report(f"train_step_{arch}", us, f"{tok_s:.0f}tok/s",
               op="train_step", arch=arch, tok_per_s=round(tok_s, 1))

    # ---- colocated decode throughput (continuous batching) --------------- #
    from repro.launch.serve import Request, Server

    cfg = SMOKE["qwen3-4b"]
    model = build_model(cfg)
    params, _ = model.init(ctx, jax.random.PRNGKey(0))
    if want("serve"):
        server = Server(model, ctx, params, batch_size=8, cache_len=96)
        rng = np.random.default_rng(0)
        for rid in range(16):
            server.submit(Request(
                rid=rid, prompt=rng.integers(0, cfg.vocab, 16).tolist(),
                max_new=16))
        stats = server.run_until_drained()
        us = stats["wall_s"] / max(stats["decoded_tokens"], 1) * 1e6
        report("serve_decode_qwen3", us, f"{stats['tok_per_s']:.1f}tok/s",
               op="serve_decode", tok_per_s=round(stats["tok_per_s"], 1),
               p50_latency_s=round(stats["p50_latency_s"], 4))
        report("serve_p50_ttft", stats["p50_ttft_s"] * 1e6,
               f"{stats['requests']}req", op="serve_ttft",
               requests=stats["requests"])

    # ---- disaggregated serving: prefill pool -> KV put -> decode pool ----- #
    # (only when the forced host device count allows >= 2 ranks)
    if not want("disagg"):
        pass
    elif jax.device_count() >= 4:
        from repro.serving.disagg import DisaggCluster

        cluster = DisaggCluster(
            model, ctx, params, n_prefill=2, n_decode=2,
            decode_batch=4, cache_len=64,
        )
        rng = np.random.default_rng(1)
        for rid in range(12):
            cluster.submit(Request(
                rid=rid, prompt=rng.integers(0, cfg.vocab, 16).tolist(),
                max_new=12,
            ))
        d = cluster.run_until_drained()
        us = d["wall_s"] / max(d["decoded_tokens"], 1) * 1e6
        report("serve_disagg_decode", us, f"{d['tok_per_s']:.1f}tok/s",
               op="serve_disagg", tok_per_s=round(d["tok_per_s"], 1),
               requests=d["requests"],
               p50_latency_s=round(d["p50_latency_s"], 4),
               p99_latency_s=round(d["p99_latency_s"], 4),
               p50_ttft_s=round(d["p50_ttft_s"], 4))
        report("serve_disagg_kv_goodput", d["kv_bytes_per_s"] / 1e6,
               f"{d['kv_transfers']}x{d['kv_block_bytes']}B", unit="mb_s",
               op="serve_disagg_kv",
               kv_bytes_per_sec=round(d["kv_bytes_per_s"], 1),
               kv_transfers=d["kv_transfers"],
               kv_block_bytes=d["kv_block_bytes"],
               kv_plan=d["kv_plan"], acked=d["kv_acked"])
        assert d["kv_acked"] == d["kv_transfers"]
    else:
        print("serve_disagg skipped: needs >= 4 host devices")

    # ---- paged KV pool: paged vs dense decode, page traffic, overlap ------ #
    if want("paged"):
        paged_sections(report)

    # ---- tiered KV memory: oversubscription + swap/recompute crossover ---- #
    if want("oversub"):
        oversub_sections(report)

    # ---- tensor-parallel decode groups: memory aggregation win ------------ #
    if want("tp"):
        tp_sections(report)

    # ---- observability: tracing tax + cost-model feedback loop ------------ #
    if want("obs"):
        obs_sections(report)

    if json_path:
        if sections is None:
            write_artifact(RESULT, json_path)
        else:
            merge_artifact(RESULT, json_path)
        append_history(RESULT, "BENCH_history.jsonl")
    print("TRAIN_SERVE_BENCH_DONE")


def paged_sections(report) -> None:
    """The global-paged-KV-pool section of ``BENCH_serve.json``:

    - paged vs dense colocated decode throughput (same burst, token parity
      asserted — the pool must be free),
    - disaggregated page traffic: page-fetch bytes/sec + prefix-hit rate
      on a burst with shared prompt prefixes,
    - page-fetch/compute overlap: split-phase vectored page get
      (``get_nbv``) overlapped with the paged-attention decode step vs the
      same fetch done blocking.
    """
    from repro.configs.registry import SMOKE
    from repro.launch.serve import PagedServer, Request, Server
    from repro.models.build import build_model
    from repro.parallel.ctx import RunCtx

    ctx = RunCtx(mesh=None, remat="none")
    cfg = SMOKE["qwen3-4b"]
    model = build_model(cfg)
    params, _ = model.init(ctx, jax.random.PRNGKey(0))

    def burst(n=12, shared_prefix=16):
        rng = np.random.default_rng(5)
        shared = rng.integers(0, cfg.vocab, shared_prefix).tolist()
        reqs = []
        for rid in range(n):
            if rid % 3 == 0:  # every third request rides the warm prefix
                prompt = shared + rng.integers(0, cfg.vocab, 2).tolist()
            else:
                prompt = rng.integers(0, cfg.vocab, 16).tolist()
            reqs.append(Request(rid=rid, prompt=prompt, max_new=12))
        return reqs

    results = {}
    for kind in ("dense", "paged"):
        if kind == "dense":
            server = Server(model, ctx, params, batch_size=8, cache_len=96)
        else:
            server = PagedServer(model, ctx, params, batch_size=8,
                                 cache_len=96, page_tokens=8)
        # warm the prefill/decode programs so the throughput row measures
        # serving, not XLA compile time (both kinds get the same warmup);
        # max_new=12 crosses a page boundary while the pool is already
        # device-resident, compiling the page-patch program too
        server.submit(Request(rid=10_000, prompt=burst()[0].prompt[:16],
                              max_new=12))
        server.run_until_drained()
        server.finished.clear()
        for req in burst():
            server.submit(req)
        stats = server.run_until_drained()
        results[kind] = {r.rid: r.out for r in server.finished}
        us = stats["wall_s"] / max(stats["decoded_tokens"], 1) * 1e6
        extra = {}
        if kind == "paged":
            extra = {k: v for k, v in stats.items() if k.startswith("pool_")}
        report(f"serve_{kind}_decode", us,
               f"{stats['tok_per_s']:.1f}tok/s", op=f"serve_{kind}",
               tok_per_s=round(stats["tok_per_s"], 1), **extra)
    assert results["dense"] == results["paged"]  # token parity, always

    # disaggregated page traffic (prefix sharing across the handoff)
    if jax.device_count() >= 4:
        from repro.serving.disagg import DisaggCluster

        cluster = DisaggCluster(
            model, ctx, params, n_prefill=2, n_decode=2,
            decode_batch=4, cache_len=64, paged=True, page_tokens=8,
        )
        for req in burst():
            cluster.submit(req)
        d = cluster.run_until_drained()
        report("serve_disagg_paged_goodput", d["kv_bytes_per_s"] / 1e6,
               f"{d['kv_pages_sent']}x{d['page_bytes']}B", unit="mb_s",
               op="serve_disagg_paged",
               tok_per_s=round(d["tok_per_s"], 1),
               kv_pages_sent=d["kv_pages_sent"],
               kv_pages_shared=d["kv_pages_shared"],
               prefix_hit_rate=round(d["prefix_hit_rate"], 4),
               page_bytes=d["page_bytes"],
               kv_bytes_per_sec=round(d["kv_bytes_per_s"], 1))
        assert d["kv_acked"] == d["kv_transfers"]
        assert d["kv_pages_shared"] > 0
    else:
        print("serve_disagg_paged skipped: needs >= 4 host devices")

    # page-fetch/compute overlap (the reason decode wants get_nbv)
    if jax.device_count() >= 2:
        overlap_bench(report)
    else:
        print("paged_fetch_overlap skipped: needs >= 2 host devices")


def oversub_sections(report) -> None:
    """The tiered-KV-memory section of ``BENCH_serve.json``:

    - tok/s and p99 request latency at 1.0x / 1.5x / 2.0x pool pressure
      (peak concurrent page demand over physical pool pages) on the
      SLO-scheduled colocated :class:`PagedServer` — the pressured runs
      preempt (swap to the memory tier / recompute-replay) and must stay
      token-identical to the unpressured run,
    - the swap-vs-recompute crossover: the generated length at which two
      vectored transfers of the victim's pages become cheaper than
      replaying the decode (recompute cost grows per generated token;
      below the crossover recompute wins), under the measured
      ``BENCH_gas.json`` β model.
    """
    from repro.configs.registry import SMOKE
    from repro.core import sched as core_sched
    from repro.launch.serve import PagedServer, Request
    from repro.models.build import build_model
    from repro.parallel.ctx import RunCtx

    ctx = RunCtx(mesh=None, remat="none")
    cfg = SMOKE["qwen3-4b"]
    model = build_model(cfg)
    params, _ = model.init(ctx, jax.random.PRNGKey(0))
    batch, cache_len, page_tokens = 4, 64, 8
    n_pages = cache_len // page_tokens
    peak_demand = batch * n_pages  # every row at a full table

    def burst():
        # long generations so every concurrent row grows toward a full
        # page table: peak demand actually reaches batch * n_pages and
        # the pressured pools must preempt (swap or recompute)
        rng = np.random.default_rng(9)
        reqs = []
        for rid in range(10):
            prompt_len = int(rng.integers(10, 30))
            max_new = int(rng.integers(24, 34))
            max_new = min(max_new, cache_len - prompt_len)
            reqs.append(Request(
                rid=rid,
                prompt=rng.integers(0, cfg.vocab, prompt_len).tolist(),
                max_new=max_new,
            ))
        return reqs

    baseline = None
    for pressure in (1.0, 1.5, 2.0):
        pool_pages = max(n_pages + 1, int(round(peak_demand / pressure)))
        server = PagedServer(model, ctx, params, batch, cache_len,
                             page_tokens=page_tokens, n_pool_pages=pool_pages)
        for req in burst():
            server.submit(req)
        stats = server.run_until_drained(max_ticks=2000)
        toks = {r.rid: r.out for r in server.finished}
        if baseline is None:
            baseline = toks
        else:
            assert toks == baseline  # preemption is semantics-transparent
        if pressure >= 1.5:
            # the pressured pools are too small for peak demand: the run
            # is only meaningful if the scheduler actually preempted
            assert stats["sched_swaps"] + stats["sched_recomputes"] >= 1, (
                pressure, stats["sched_swaps"], stats["sched_recomputes"])
        lat = sorted(r.t_done - r.t_enqueue for r in server.finished)
        p99 = lat[min(len(lat) - 1, int(0.99 * len(lat)))] if lat else 0.0
        us = stats["wall_s"] / max(stats["decoded_tokens"], 1) * 1e6
        report(
            f"serve_oversub_{pressure:.1f}x", us,
            f"{stats['tok_per_s']:.1f}tok/s", op="serve_oversub",
            pressure=pressure, pool_pages=pool_pages,
            tok_per_s=round(stats["tok_per_s"], 1),
            p99_latency_s=round(p99, 4),
            evictions=stats["sched_evictions"],
            swaps=stats["sched_swaps"],
            recomputes=stats["sched_recomputes"],
            resumes=stats["sched_resumes"],
            swap_pages=stats["tier_swapped_out_pages"],
        )

    # swap-vs-recompute crossover under the measured beta model
    from repro.serving.scheduler import swap_or_recompute
    from repro.serving.pool import PagedLayout

    costs = core_sched.load_costs("BENCH_gas.json")
    cost = costs.get("xla") or next(iter(costs.values()))
    layout = PagedLayout.from_struct(
        model.kv_block_struct(ctx, prompt_len=4, cache_len=cache_len),
        cache_len=cache_len, page_tokens=page_tokens,
    )
    crossover = None
    for g in range(0, 4096):
        mode, swap_us, rec_us = swap_or_recompute(
            n_pages, layout.page_bytes, g, cost
        )
        if mode == "swap":
            crossover = g
            break
    report(
        "serve_swap_recompute_crossover",
        float(crossover if crossover is not None else -1),
        f"beta={cost.beta_us_per_kib}us/KiB", unit="tokens",
        op="serve_oversub", page_bytes=layout.page_bytes,
        pages_per_request=n_pages,
        crossover_generated_tokens=crossover,
        alpha_us=cost.alpha_us, beta_us_per_kib=cost.beta_us_per_kib,
    )

    # ---- admission backpressure A/B: SLO health closing the loop ---------- #
    # Two identical oversubscribed runs — a bulk class (priority 0, long
    # decodes, arriving continuously: more demand than the pool fits)
    # against a latency class (priority 2, short decodes, finite
    # deadlines) streaming in AFTER the bulk has saturated the pool.
    # The only difference between the arms is whether the
    # HealthMonitor's backpressure floor is honoured.  Priority-major
    # admission already gives a QUEUED latency request first claim on a
    # freed slot in both arms — what it cannot do is keep capacity free
    # between latency arrivals: without the floor, every slot freed
    # while the latency queue is momentarily empty is recaptured by a
    # ~30-tick bulk decode, and the next latency arrival pays a
    # growth-eviction wait all over again.  With the floor (resident
    # latency requests hover at-risk on their tight TPOT deadline, so
    # it stays up through the stream), evicted bulk cannot resume into
    # freed capacity and the stream's TTFT collapses to ~1 tick.
    # Preemption is semantics-transparent, so both arms must produce
    # identical tokens (equal throughput) — the arms differ only in
    # WHEN work ran, i.e. in the latency class's TTFT/TPOT.
    from repro.obs.health import HealthMonitor
    from repro.serving.scheduler import SLO

    bulk_len, lat_len = 16, 8
    bulk_new, lat_new = 32, 8
    pool_bp = max(n_pages + 1, int(round(peak_demand / 1.5)))

    def bp_traffic(ttft_dl, tpot_dl):
        rng = np.random.default_rng(13)
        bulk = [
            Request(rid=100 + i,
                    prompt=rng.integers(0, cfg.vocab, bulk_len).tolist(),
                    max_new=bulk_new, slo=SLO(priority=0))
            for i in range(14)
        ]
        lat = [
            Request(rid=200 + i,
                    prompt=rng.integers(0, cfg.vocab, lat_len).tolist(),
                    max_new=lat_new,
                    slo=SLO(priority=2, ttft_deadline_s=ttft_dl,
                            tpot_deadline_s=tpot_dl))
            for i in range(16)
        ]
        # bulk saturates the pool first and keeps dripping so its queue
        # never empties; the latency stream starts after saturation
        plan = {0: bulk[:3], 2: bulk[3:5], 6: bulk[5:8]}
        for i, r in enumerate(bulk[8:]):
            plan.setdefault(30 + 8 * i, []).append(r)
        for i, r in enumerate(lat):
            plan.setdefault(24 + 4 * i, []).append(r)
        return plan, lat

    def bp_arm(backpressure, ttft_dl, tpot_dl):
        # risk_frac 0.5 with tpot_dl ~1.6 ticks keeps a healthily-decoding
        # latency request at-risk (steady risk ~0.6) without violating —
        # the floor holds through the stream instead of flapping
        mon = HealthMonitor(backpressure=backpressure, risk_frac=0.5)
        server = PagedServer(model, ctx, params, batch, cache_len,
                             page_tokens=page_tokens, n_pool_pages=pool_bp,
                             health=mon)
        # warm both prompt-length prefills and the full-width decode so
        # deadlines measure scheduling, not XLA compilation
        rng = np.random.default_rng(11)
        for rid, plen, mnew in ((90_000, bulk_len, bulk_new),
                                (90_001, lat_len, lat_new)):
            server.submit(Request(
                rid=rid, prompt=rng.integers(0, cfg.vocab, plen).tolist(),
                max_new=mnew))
        server.run_until_drained(max_ticks=2000)
        server.finished.clear()

        plan, lat = bp_traffic(ttft_dl, tpot_dl)
        t0 = time.perf_counter()
        for tick in range(max(plan) + 1):
            for r in plan.get(tick, ()):
                server.submit(r)
            server.step()
        stats = server.run_until_drained(max_ticks=4000)
        wall = time.perf_counter() - t0
        fin = {r.rid: r for r in server.finished}
        lat_fin = [fin[r.rid] for r in lat]
        ttfts = sorted(r.t_first - r.t_enqueue for r in lat_fin)
        return {
            "outs": {rid: r.out for rid, r in fin.items()},
            "toks": sum(len(r.out) for r in fin.values()),
            "wall_s": wall,
            "p99_ttft_s": ttfts[min(len(ttfts) - 1,
                                    int(0.99 * len(ttfts)))],
            "ttft_violations": sum(
                1 for r in lat_fin if r.t_first - r.t_enqueue > ttft_dl),
            "slo_violations": int(
                mon.registry.counter("slo_violations").value),
            "deferrals": stats["sched_deferrals"],
            "swaps": stats["sched_swaps"],
            "recomputes": stats["sched_recomputes"],
        }

    # calibrate deadlines from a healthy warm tick so they track this
    # machine, not a hardcoded wall; both arms share the same numbers.
    # The warm-up must drain a full-length request first: the decode jit
    # recompiles as the page-table width crosses its 4-page buckets, and
    # a compile landing inside the timed window would inflate per_tick
    # ~25x (and with it every deadline, leaving nothing ever at risk)
    cal = PagedServer(model, ctx, params, batch, cache_len,
                      page_tokens=page_tokens, n_pool_pages=pool_bp)
    rng = np.random.default_rng(11)
    cal.submit(Request(rid=94_999,
                       prompt=rng.integers(0, cfg.vocab, bulk_len).tolist(),
                       max_new=bulk_new))
    cal.run_until_drained(max_ticks=2000)
    cal.finished.clear()
    for i in range(batch):
        cal.submit(Request(
            rid=95_000 + i,
            prompt=rng.integers(0, cfg.vocab, bulk_len).tolist(),
            max_new=bulk_new))
    for _ in range(3):
        cal.step()  # settle admissions; jits are already warm
    t0 = time.perf_counter()
    for _ in range(8):
        cal.step()
    per_tick = (time.perf_counter() - t0) / 8
    cal.run_until_drained(max_ticks=2000)
    ttft_dl = 6.0 * per_tick
    tpot_dl = 1.6 * per_tick

    arms = {bp: bp_arm(bp, ttft_dl, tpot_dl) for bp in (False, True)}
    # equal throughput: scheduling may move work in time, never change it
    assert arms[True]["outs"] == arms[False]["outs"]
    assert arms[True]["deferrals"] >= 1, "backpressure arm never deferred"
    assert (arms[True]["ttft_violations"]
            <= arms[False]["ttft_violations"]), (
        arms[True]["ttft_violations"], arms[False]["ttft_violations"])
    for bp in (False, True):
        a = arms[bp]
        name = ("serve_oversub_backpressure" if bp
                else "serve_oversub_no_backpressure")
        report(
            name, a["p99_ttft_s"] * 1e6,
            f"{a['ttft_violations']} TTFT violations, "
            f"{a['toks']} toks", op="serve_oversub_bp",
            backpressure=bp, pool_pages=pool_bp,
            ttft_deadline_s=round(ttft_dl, 4),
            p99_ttft_s=round(a["p99_ttft_s"], 4),
            ttft_violations=a["ttft_violations"],
            slo_violations=a["slo_violations"],
            deferrals=a["deferrals"], swaps=a["swaps"],
            recomputes=a["recomputes"], tokens=a["toks"],
            wall_s=round(a["wall_s"], 4),
        )


def tp_sections(report) -> None:
    """The tensor-parallel-decode section of ``BENCH_serve.json``.

    Run on a scaled-up smoke config (~8M params) where the decode step
    is weights-bound, the regime the >= 8B configs live in: step cost is
    nearly batch-independent, so decode throughput is set by how many
    requests run CONCURRENTLY.  That is what the TP group buys on equal
    hardware — not FLOPs (every member computes 1/tp of each step, so
    aggregate compute is unchanged) but AGGREGATE MEMORY: at a fixed
    per-rank pool byte budget, head-sharded pages are ~1/tp the bytes,
    the group fits ~tp x the pages, and the decode batch scales with
    them.  Here tp=1's budget caps the batch at 4 while the tp=2
    group's aggregated pool runs batch 8.

    Every ``serve_tp_decode_tp{1,2,4}`` row carries two timings:

    - ``us_serialized``: the raw wall of the real shard_map step on this
      host — every rank's shard compute AND every all-reduce hop
      serialized back-to-back onto the local cores (this host simulates
      the group's devices on shared cores, so what it clocks is the
      group's total WORK, not its latency).
    - ``us`` (headline, feeds ``tok_per_s``): the group's RANK-CONCURRENT
      decode-step latency, ``us_serialized / tp`` — un-serializing what
      the ranks run simultaneously.  Cross-checked against
      ``us_rank_compute``, one rank's OWN step program (its head shard of
      the weights and pool, collectives elided) clocked alone: a hard
      lower bound on any rank's concurrent step, asserted to stay below
      the headline so the division never claims time the measured
      single-rank program disproves.

    Token parity vs an unsharded reference server is asserted from REAL
    ``TPPagedServer`` runs (actual planned all-reduces on the wire, all
    preemption machinery live) for every tp before any timing is
    reported.  tp=4 runs a ``n_kv_heads=4`` variant (4 does not divide
    the base config's 2 KV heads) against its own reference — its row
    shows the trend; only tp2/tp1 (same config) is the gated
    ``serve_tp_speedup`` ratio.
    """
    import dataclasses

    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.configs.registry import SMOKE
    from repro.core import sched as core_sched
    from repro.launch.serve import (PagedServer, Request, TPPagedServer,
                                    _paged_decode_views_fn,
                                    _tp_paged_decode_fn)
    from repro.models.build import build_model
    from repro.parallel import tp as tp_lib
    from repro.parallel.ctx import RunCtx
    from repro.serving.pool import PagedLayout

    ctx = RunCtx(mesh=None, remat="none")
    # weights-bound decode: scale the 405B smoke shape up until weight
    # streaming dominates per-step dispatch (~8M params, 31MB f32)
    base = SMOKE["llama3-405b"]
    base = dataclasses.replace(base, n_layers=8, d_model=256, d_ff=1024,
                               head_dim=32)
    cache_len, pt, max_batch = 64, 8, 8
    n_pages = cache_len // pt
    costs = core_sched.load_costs("BENCH_gas.json")

    def burst(cfg):
        rng = np.random.default_rng(17)
        reqs = []
        for rid in range(12):
            plen = int(rng.integers(10, 24))
            reqs.append(Request(
                rid=rid, prompt=rng.integers(0, cfg.vocab, plen).tolist(),
                max_new=min(int(rng.integers(28, 38)), cache_len - plen),
            ))
        return reqs

    def run(server, cfg):
        for req in burst(cfg):
            server.submit(req)
        stats = server.run_until_drained(max_ticks=4000)
        return {r.rid: list(r.out) for r in server.finished}, stats

    def timed_step(call, state, iters=12):
        """Per-step wall of ``call(state) -> (logits, state)``; the state
        (the pool views — donated by the real step programs) is threaded
        through so every iteration runs on a live buffer."""
        logits, state = call(state)  # compile + warm
        jax.block_until_ready(logits)
        t0 = time.perf_counter()
        for _ in range(iters):
            logits, state = call(state)
        jax.block_until_ready(logits)
        return (time.perf_counter() - t0) / iters * 1e6

    # the per-rank byte budget: one rank's pool barely fits batch 4 of
    # full pages; the tp=2 group's half-size pages fit batch 8
    base_layout = PagedLayout.from_struct(
        build_model(base).kv_block_struct(ctx, prompt_len=4,
                                          cache_len=cache_len),
        cache_len=cache_len, page_tokens=pt,
    )
    budget_bytes = (4 * n_pages + 1) * base_layout.page_bytes

    models = {}

    def get_model(cfg):
        if cfg not in models:
            model = build_model(cfg)
            params, _ = model.init(ctx, jax.random.PRNGKey(0))
            models[cfg] = (model, params, None)
        return models[cfg]

    # pass 1 — step timing at the budget-planned batch, in a clean
    # process state: the serving runs below allocate large pools and
    # churn donated buffers, which perturbs step walls clocked after
    setups = {}
    timing = {}
    for tp in (1, 2, 4):
        if jax.device_count() < tp:
            print(f"serve_tp_decode_tp{tp} skipped: needs >= {tp} devices")
            continue
        cfg = base if tp <= 2 else dataclasses.replace(base, n_kv_heads=4)
        model, params, _ = get_model(cfg)
        layout = PagedLayout.from_struct(
            model.kv_block_struct(ctx, prompt_len=4, cache_len=cache_len),
            cache_len=cache_len, page_tokens=pt,
        )
        shard_layout, _cols = layout.shard_heads(tp, cfg.n_kv_heads)
        n_pool = max(n_pages + 1, budget_bytes // shard_layout.page_bytes)
        batch = max(1, min(max_batch, n_pool // n_pages))
        setups[tp] = (cfg, layout, shard_layout, n_pool, batch)
        tok = jnp.zeros((batch, 1), jnp.int32)
        pos = jnp.full((batch,), 8, jnp.int32)
        tab = jnp.zeros((batch, n_pages), jnp.int32)
        if tp == 1:
            fn = _paged_decode_views_fn(model, ctx, layout)
            views0 = layout.decode_views(
                jnp.zeros((n_pool + 1, layout.page_elems), jnp.float32))
            serialized = compute = timed_step(
                lambda v: fn(params, tok, pos, v, tab), views0)
        else:
            # the REAL planned-collective shard_map program the TP
            # server decodes with, clocked on this host
            mesh = make_mesh((tp,), ("tp",))
            sharding = NamedSharding(mesh, P("tp"))
            sparams = jax.device_put(
                tp_lib.stack_shards(params, tp), sharding)
            fn = _tp_paged_decode_fn(model, ctx, shard_layout, tp, "xla",
                                     mesh)
            dev0 = jax.device_put(
                jnp.zeros((tp, n_pool + 1, shard_layout.page_elems),
                          jnp.float32), sharding)
            serialized = timed_step(
                lambda v: fn(sparams, tok, pos, v, tab), dev0)
            # one rank's OWN step program (its head shard of the weights
            # and pool, collectives elided), clocked alone
            group = tp_lib.TPGroup(tp, lambda x: x)
            p0 = jax.device_put(tp_lib.shard_decode_params(params, tp, 0))

            @jax.jit
            def rank_step(p, token, position, views, tables=tab):
                return model.decode_step_paged(
                    p, ctx, token, position, views, tables, tp=group)

            sv0 = shard_layout.decode_views(
                jnp.zeros((n_pool + 1, shard_layout.page_elems),
                          jnp.float32))
            compute = timed_step(
                lambda v: rank_step(p0, tok, pos, v), sv0)
        timing[tp] = (serialized, compute)

    # pass 2 — real serving runs: actual planned all-reduces on the
    # wire, all preemption machinery live, token parity asserted
    tok_s = {}
    for tp, (cfg, layout, shard_layout, n_pool, batch) in setups.items():
        model, params, ref_toks = get_model(cfg)
        if ref_toks is None:
            ref = PagedServer(model, ctx, params, batch, cache_len,
                              page_tokens=pt)
            ref_toks, _ = run(ref, cfg)
            models[cfg] = (model, params, ref_toks)
        kw = dict(page_tokens=pt, n_pool_pages=n_pool)
        if tp == 1:
            server = PagedServer(model, ctx, params, batch, cache_len, **kw)
        else:
            server = TPPagedServer(model, ctx, params, batch, cache_len,
                                   tp=tp, tp_backend="xla", **kw)
        toks, stats = run(server, cfg)
        assert toks == ref_toks, f"tp={tp} token parity failed"

        serialized, compute = timing[tp]
        concurrent = serialized / tp
        if tp == 1:
            ar_us = 0.0
            ar_note = "none (single rank)"
        else:
            assert compute <= concurrent * 1.05, (
                f"tp={tp}: one rank's measured step ({compute:.0f}us) "
                f"exceeds the un-serialized group step ({concurrent:.0f}us)"
            )
            # 2 partial-sum all-reduces per layer (attention wo + mlp
            # w2), (batch, 1, d_model) f32 payloads; their serialized
            # in-program cost is the wall the shard compute can't explain
            ar_us = max(0.0, serialized - tp * compute)
            plan = core_sched.plan_collective(
                "all_reduce", nbytes=batch * cfg.d_model * 4,
                n_nodes=tp, costs=costs)
            n_ar = 2 * cfg.n_layers
            ar_note = (f"{n_ar} x {plan.algorithm}, "
                       f"~{ar_us / n_ar:.0f}us each serialized in-program")
        tps = batch / concurrent * 1e6
        tok_s[tp] = tps
        report(f"serve_tp_decode_tp{tp}", concurrent,
               f"{tps:.0f}tok/s @batch{batch}", op="serve_tp",
               tp=tp, tok_per_s=round(tps, 1), batch=batch,
               us_serialized=round(serialized, 1),
               us_rank_compute=round(compute, 1),
               tok_per_s_serialized=round(batch / serialized * 1e6, 1),
               serve_tok_per_s=round(stats["tok_per_s"], 1),
               allreduce_us=round(ar_us, 1), allreduce_plan=ar_note,
               pool_pages=n_pool,
               shard_page_bytes=shard_layout.page_bytes,
               budget_bytes=budget_bytes,
               n_kv_heads=cfg.n_kv_heads)
    if 1 in tok_s and 2 in tok_s:
        speedup = tok_s[2] / max(tok_s[1], 1e-9)
        report("serve_tp_speedup", speedup,
               f"tp2 {tok_s[2]:.0f} vs tp1 {tok_s[1]:.0f} tok/s", unit="x",
               op="serve_tp", tp_from=1, tp_to=2,
               tok_per_s_tp1=round(tok_s[1], 1),
               tok_per_s_tp2=round(tok_s[2], 1))


def overlap_bench(report) -> None:
    """Measure the split-phase win of nonblocking page prefetch.

    A decode tick that needs K remote page batches can either issue
    *blocking* gets — each get's sync completes before the next statement
    runs, serialising host dispatch with the wire — or initiate all K
    vectored gets nonblocking, run the paged-attention kernel over local
    pages, and sync the handles afterwards.  The overlap variant pipelines
    initiation with execution (the GASNet split-phase idiom); the gap is
    the dispatch+sync latency the blocking semantics cannot hide.
    """
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from repro.core import gasnet
    from repro.kernels import ops
    from repro.serving import pool as pool_lib

    n = 2
    B, Hq, Hkv, D, T, NP = 4, 8, 2, 64, 8, 8
    pages_per_rank = 64
    page_elems = T * Hkv * D * 2  # K and V halves of one page
    n_batches, pages_per_batch = 8, 4
    pmap = pool_lib.PoolMap(n, pages_per_rank, page_elems)
    mesh = make_mesh((n,), ("node",))
    ctx_gas = gasnet.Context(mesh, node_axis="node", backend="xla")

    rng = np.random.default_rng(0)
    seg = jax.device_put(
        jnp.asarray(
            rng.normal(size=(n, pages_per_rank * page_elems)), jnp.float32
        ),
        jax.sharding.NamedSharding(mesh, P("node")),
    )
    q = jnp.asarray(rng.normal(size=(B, Hq, D)), jnp.float32)
    kv_pages = jnp.asarray(
        rng.normal(size=(pages_per_rank, T, Hkv, D)), jnp.float32
    )
    v_pages = jnp.asarray(
        rng.normal(size=(pages_per_rank, T, Hkv, D)), jnp.float32
    )
    table = jnp.asarray(
        rng.integers(0, pages_per_rank, (B, NP)), jnp.int32
    )
    lengths = jnp.full((B,), NP * T, jnp.int32)

    def make_fetch(offsets):
        def prog(node, s):
            handles, _ = pool_lib.fetch_pages(
                node, s, offsets, frm=gasnet.Shift(1),
                page_elems=page_elems,
            )
            return pool_lib.sync_fetch(node, handles)[None]

        return jax.jit(shard_map(
            lambda s: prog(ctx_gas.make_node(), s),
            mesh=mesh, in_specs=(P("node"),), out_specs=P("node"),
            check_vma=False,
        ))

    fetch_fns = []
    fetch_ids = []
    for _ in range(n_batches):
        ids = [int(x) for x in rng.integers(0, pages_per_rank, pages_per_batch)]
        fetch_ids.extend(ids)
        fetch_fns.append(make_fetch(
            jnp.asarray([pmap.offset(g) for g in ids], jnp.int32)
        ))
    attn_fn = jax.jit(lambda q, kp, vp, t, l: ops.paged_attention(
        q, kp, vp, t, l, impl="pallas"))

    for fn in fetch_fns:
        jax.block_until_ready(fn(seg))
    jax.block_until_ready(attn_fn(q, kv_pages, v_pages, table, lengths))

    def run_blocking():
        fetched = [None] * n_batches
        for i, fn in enumerate(fetch_fns):
            fetched[i] = fn(seg)
            jax.block_until_ready(fetched[i])  # blocking-get semantics
        out = attn_fn(q, kv_pages, v_pages, table, lengths)
        jax.block_until_ready(out)
        return out, fetched

    def run_overlap():
        # initiate every get nonblocking, decode, then sync the handles
        fetched = [fn(seg) for fn in fetch_fns]
        out = attn_fn(q, kv_pages, v_pages, table, lengths)
        jax.block_until_ready(out)
        for f in fetched:
            jax.block_until_ready(f)
        return out, fetched

    samples = {"blocking": [], "overlap": []}
    outs = {}
    iters = 30
    run_blocking()  # warm both programs
    run_overlap()
    for _ in range(iters):
        # interleave the variants so machine-load drift during the run
        # lands on both equally instead of biasing whichever ran last
        for kind, run in (("blocking", run_blocking), ("overlap", run_overlap)):
            t0 = time.perf_counter()
            o = run()
            samples[kind].append(time.perf_counter() - t0)
            out, fetched = o
            outs[kind] = [np.asarray(out)] + [np.asarray(f) for f in fetched]
    # best-of-N: the structural cost each variant cannot avoid, with
    # scheduler noise (which only ever adds time) stripped out
    times = {k: float(np.min(v)) * 1e6 for k, v in samples.items()}
    for a, b in zip(outs["blocking"], outs["overlap"]):
        np.testing.assert_allclose(a, b, rtol=1e-6)
    gap = times["blocking"] / max(times["overlap"], 1e-9)
    fetch_bytes = len(fetch_ids) * page_elems * 4
    report("paged_fetch_blocking", times["blocking"],
           f"{fetch_bytes}B fetched in {n_batches} blocking gets",
           op="paged_overlap", fetch_bytes=fetch_bytes,
           n_batches=n_batches)
    report("paged_fetch_overlap", times["overlap"],
           f"{gap:.2f}x vs blocking", op="paged_overlap",
           fetch_bytes=fetch_bytes, n_batches=n_batches,
           overlap_gap=round(gap, 3))


def obs_sections(report) -> None:
    """The observability section of ``BENCH_serve.json``:

    - ``obs_overhead``: paged-decode wall time with tracing fully ENABLED
      over the same burst with the no-op recorder installed (the
      production default).  The enabled run bounds the instrumentation
      tax from above — the disabled path runs only the ``active()`` +
      ``.enabled`` guards and the profiler host spans, which are
      strictly cheaper — so gating the
      ratio (``check_serve_perf``: < 1.02x) keeps tracing-off overhead
      under the 2% budget by construction.
    - the cost-model feedback loop: real executed transfers (warmed,
      blocking segmented puts at three payload sizes) recorded as
      ``cat="transfer"`` spans, from which ``EngineCost.fit_from_trace``
      refits (α, β).  Rows report the shipped DEFAULT model's
      predicted-vs-measured error and the refit's residual — the
      measurement closing the loop back into
      ``plan_p2p``/``plan_collective``.  Thin traces degrade
      to a reported ``fit: insufficient-data`` note, never a crash.
    """
    from repro.configs.registry import SMOKE
    from repro.core import gasnet
    from repro.core import sched as core_sched
    from repro.launch.serve import PagedServer, Request
    from repro.models.build import build_model
    from repro.obs import trace as obs_trace
    from repro.parallel.ctx import RunCtx

    ctx = RunCtx(mesh=None, remat="none")
    cfg = SMOKE["qwen3-4b"]
    model = build_model(cfg)
    params, _ = model.init(ctx, jax.random.PRNGKey(0))

    def burst(base_rid, n=12):
        rng = np.random.default_rng(7)
        return [
            Request(rid=base_rid + rid,
                    prompt=rng.integers(0, cfg.vocab, 16).tolist(),
                    max_new=12)
            for rid in range(n)
        ]

    def make_server():
        server = PagedServer(model, ctx, params, batch_size=8,
                             cache_len=96, page_tokens=8)
        server.submit(Request(rid=10_000,
                              prompt=burst(0)[0].prompt, max_new=12))
        server.run_until_drained()  # warm prefill/decode/page-patch jits
        server.finished.clear()
        return server

    servers = {False: make_server(), True: make_server()}
    walls = {False: [], True: []}
    for rep in range(6):
        # interleave the variants so machine-load drift lands on both
        for enabled in (False, True):
            server = servers[enabled]
            reqs = burst((1 + rep) * 100)
            if enabled:
                obs_trace.enable(capacity=1 << 16)
            try:
                for req in reqs:
                    server.submit(req)
                t0 = time.perf_counter()
                server.run_until_drained()
                walls[enabled].append(time.perf_counter() - t0)
            finally:
                obs_trace.disable()
            server.finished.clear()
    # best-of-N: scheduler noise only ever adds time
    t_off = min(walls[False])
    t_on = min(walls[True])
    overhead = t_on / max(t_off, 1e-9)
    report("obs_overhead", t_on * 1e6, f"{overhead:.3f}x vs tracing off",
           unit="x", op="obs_overhead", overhead_x=round(overhead, 4),
           wall_on_s=round(t_on, 4), wall_off_s=round(t_off, 4))

    # ---- cost-model feedback: measured transfer spans -> refit ------------ #
    if jax.device_count() < 2:
        print("obs cost-model rows skipped: needs >= 2 host devices")
        return
    n = 2
    mesh = make_mesh((n,), ("node",))
    gctx = gasnet.Context(mesh, node_axis="node", backend="xla")
    tr = obs_trace.enable(capacity=4096)
    sizes = (1 << 16, 1 << 18, 1 << 20)  # 64 KiB, 256 KiB, 1 MiB
    try:
        for size in sizes:
            n_el = size // 4
            aspace = gctx.address_space()
            aspace.register(f"obs{size}", (n_el,), jnp.float32)
            seg = aspace.alloc(f"obs{size}")

            def put_prog(node, seg, n_el=n_el):
                data = jnp.ones((n_el,), jnp.float32) * node.my_id
                return node.put(seg, data, to=gasnet.Shift(1), index=0)

            # jit ONCE per size (spmd builds a fresh closure per call, so
            # its internal jit cache never hits): the measured spans must
            # time executed wire work, not retracing
            fn = jax.jit(
                lambda s, prog=put_prog: gctx.spmd(prog, s, jit=False)
            )

            def run(s, fn=fn):
                return jax.block_until_ready(fn(s))

            for _ in range(3):
                run(seg)  # warm: the spans must time execution, not XLA
            for _ in range(4):
                with tr.span(f"put_{size}", cat="transfer", bytes=size):
                    run(seg)
        spans = list(tr.spans(cat="transfer"))
    finally:
        obs_trace.disable()
    cost0 = core_sched.DEFAULT_COSTS["xla"]
    err0 = cost0.model_error(spans)
    report("obs_cost_model_err", err0 * 100, "DEFAULT α/β vs measured",
           unit="pct", op="obs_cost", model_error=round(err0, 4),
           alpha_us=cost0.alpha_us, beta_us_per_kib=cost0.beta_us_per_kib)

    fit, note = core_sched.try_fit_from_trace(spans)
    if fit is None:
        print(f"obs_cost_refit_err skipped: {note}")
        return
    err1 = fit.model_error(spans)
    report("obs_cost_refit_err", err1 * 100,
           f"fit α={fit.alpha_us:.1f}us β={fit.beta_us_per_kib:.3f}",
           unit="pct", op="obs_cost", model_error=round(err1, 4),
           alpha_us=round(fit.alpha_us, 2),
           beta_us_per_kib=round(fit.beta_us_per_kib, 4),
           n_spans=len(spans), note=note)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--json", nargs="?", const="BENCH_serve.json", default=None,
        metavar="PATH",
        help="write the machine-readable artifact (default: BENCH_serve.json)",
    )
    ap.add_argument(
        "--sections", default=None, metavar="A,B,...",
        help=f"run only these sections (of {','.join(SECTIONS)}) and MERGE "
             "their rows into the --json artifact instead of rewriting it",
    )
    args = ap.parse_args()
    picked = None
    if args.sections is not None:
        picked = tuple(s.strip() for s in args.sections.split(",") if s.strip())
        unknown = [s for s in picked if s not in SECTIONS]
        if unknown:
            ap.error(f"unknown sections {unknown}; choose from {SECTIONS}")
    from repro.launch import compile_cache

    compile_cache.enable()
    main(json_path=args.json, sections=picked)
