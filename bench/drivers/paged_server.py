"""Drives the colocated paged server (``repro.launch.serve.PagedServer``)
with open-loop chat traffic, and checks what it served against the
configuration's plain reference.

Set-up makes the weights on the device from the seed
(``bench.weights``), builds the server, and warms up every program the
window will run: one prefill per prompt length the mix can send, the
decode step at the full page-table width, the page-patch program and the
eager cache flatten.  The server's page-table width is a high-water mark
that only grows; a long-running server sits at its largest width, which
is where set-up puts it, so that no width compiles in the window.

The window submits each request when it is due, ticks the server while
it has work, and stamps every output token on the host clock when the
tick that made it returns.  TTFT runs from the request's due time, so a
stall counts against every request behind it.
"""
from __future__ import annotations

import dataclasses
import gc
import os
import resource
import sys
import time

import numpy as np

from bench import compiles, traffic, weights
from bench.record import Record
from bench.trace import Profile, span

WARMUP_RID = 1 << 30


def arch_config(cfg: dict):
    """The program's configuration object from the published keys."""
    import jax.numpy as jnp

    from repro.models.common import ArchConfig

    if cfg["model_type"] != "qwen3":
        raise ValueError(f"no mapping for model_type {cfg['model_type']!r}")
    if float(cfg["rms_norm_eps"]) != 1e-6 or cfg["hidden_act"] != "silu":
        raise ValueError("the program's dense block has eps 1e-6 and silu")
    return ArchConfig(
        name=cfg["name"], family="dense",
        n_layers=int(cfg["num_hidden_layers"]),
        d_model=int(cfg["hidden_size"]),
        n_heads=int(cfg["num_attention_heads"]),
        n_kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]),
        d_ff=int(cfg["intermediate_size"]),
        vocab=int(cfg["vocab_size"]),
        pattern=("global",), qk_norm=True,
        rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype=jnp.dtype(cfg["torch_dtype"]),
    )


@dataclasses.dataclass
class State:
    cell: dict
    seed: int
    devs: list
    srv: object = None
    params: object = None


def build_server(cfg: dict, settings: dict, seed: int):
    from repro.launch.serve import PagedServer
    from repro.models.build import build_model
    from repro.parallel.ctx import RunCtx

    model = build_model(arch_config(cfg))
    ctx = RunCtx(mesh=None, remat="none")
    struct, _ = model.abstract_init(ctx)
    params = weights.make_params(struct, seed)
    srv = PagedServer(
        model, ctx, params, int(settings["batch"]),
        int(settings["cache_len"]),
        page_tokens=int(settings["page_tokens"]),
        n_pool_pages=int(settings["pool_pages"]),
    )
    # the steady-state width; the server only ever grows it
    if not isinstance(getattr(srv, "_table_width", None), int):
        raise RuntimeError("PagedServer has no integer _table_width: the "
                           "decode step's table width cannot be fixed")
    srv._table_width = srv.layout.n_pages
    return srv, params


def setup(cell: dict, seed: int, devs) -> State:
    from repro.launch.serve import Request

    cfg, settings = cell["config"], cell["settings"]
    srv, params = build_server(cfg, settings, seed)
    # warm-up: every prefill shape, the decode step and the page patches
    rng = np.random.default_rng([seed, 1])
    for j, n in enumerate(traffic.prompt_lengths(cell["mix"])):
        srv.submit(Request(
            rid=WARMUP_RID + j,
            prompt=rng.integers(0, int(cfg["vocab_size"]), n).tolist(),
            max_new=2,
        ))
    while srv._pending():
        srv.step()
    gc.collect()
    return State(cell=cell, seed=seed, devs=devs, srv=srv, params=params)


def _hook(srv, rec: Record, t0: list):
    """Wrap the server's admission, decode and patch calls with host
    spans, and count what each tick needed (from the benchmark's side):
    the context length of every live row, and the pool pages in use."""
    T = srv.layout.page_tokens
    n_pages = srv.store.stats()["n_pages"]
    prefill, decode, patch = (srv._prefill_pages, srv._decode_via_tables,
                              srv._apply_pending)

    def prefill_hook(req):
        cached = srv.store.prefix_match(req.prompt) * T
        ts = time.monotonic()
        with span("admission"):
            out = prefill(req)
        rec.host["prefills"].append(
            (len(req.prompt), cached, ts - t0[0], time.monotonic() - t0[0]))
        return out

    def decode_hook(tables):
        rec.host["decode_lengths"].append(
            [int(srv.positions[i]) + 1
             for i, r in enumerate(srv.active) if r is not None])
        rec.host["pool_pages"].append(n_pages - srv.store.stats()["n_free"])
        with span("decode"):
            return decode(tables)

    def patch_hook():
        with span("patch"):
            return patch()

    srv._prefill_pages = prefill_hook
    srv._decode_via_tables = decode_hook
    srv._apply_pending = patch_hook


def window(state: State, seconds: float, trace: bool) -> Record:
    from repro.launch.serve import Request

    cell, srv = state.cell, state.srv
    cfg, settings = cell["config"], cell["settings"]
    reqs = traffic.chat_requests(
        cell["mix"], float(settings["rate_rps"]), seconds, state.seed,
        int(cfg["vocab_size"]))
    rec = Record(config=cfg, settings=settings,
                 device_kind=state.devs[0].device_kind,
                 n_chips=len(state.devs))
    rec.host.update(prefills=[], decode_lengths=[], pool_pages=[], ticks=[],
                    late_s=[])
    t0 = [0.0]
    _hook(srv, rec, t0)
    hits0, misses0 = srv.store.prefix_hits, srv.store.prefix_misses
    prof = Profile(trace, state.devs)
    counter = compiles.Counter()

    live = {}  # rid -> (request, due time, token stamps)
    done = []
    i, n = 0, len(reqs)
    ru0 = resource.getrusage(resource.RUSAGE_SELF)
    with prof, counter:
        t0[0] = start = time.monotonic()
        end = start + seconds
        while True:
            now = time.monotonic()
            if now >= end:
                break
            while i < n and start + reqs[i].due_s <= now:
                r = reqs[i]
                obj = Request(rid=r.rid, prompt=r.prompt, max_new=r.max_new)
                with span("submit"):
                    srv.submit(obj)
                rec.host["late_s"].append(now - start - r.due_s)
                live[r.rid] = (obj, r.due_s, [])
                i += 1
            if not srv._pending():
                nxt = start + reqs[i].due_s if i < n else end
                with span("wait"):
                    time.sleep(max(0.0, min(nxt, end) - time.monotonic()))
                continue
            ts = time.monotonic()
            with span("step"):
                srv.step()
            te = time.monotonic()
            rec.host["ticks"].append((ts - start, te - start))
            for rid in list(live):
                obj, due, stamps = live[rid]
                stamps.extend([te - start] * (len(obj.out) - len(stamps)))
                if obj.t_done:
                    done.append(live.pop(rid))
        stop = time.monotonic()
    ru1 = resource.getrusage(resource.RUSAGE_SELF)
    rec.window_s = stop - start
    rec.compiles = counter.count
    rec.trace = prof.reduction()
    rec.attempted = i
    rec.failed = 0
    rec.counters["prefix_hits"] = srv.store.prefix_hits - hits0
    rec.counters["prefix_misses"] = srv.store.prefix_misses - misses0
    every = done + list(live.values())
    _hooks_fired(rec, sum(1 for o, _, _ in every if o.out))
    rec.host["tokens"] = sum(len(s) for _, _, s in every)
    rec.host["ttft_s"] = [s[0] - due for _, due, s in every if s]
    rec.host["itl_s"] = [b - a for _, _, s in every
                         for a, b in zip(s, s[1:])]
    rec.outputs = [(list(o.prompt), list(o.out), o.max_new)
                   for o, _, _ in done]
    late = np.asarray(rec.host["late_s"] or [0.0]) * 1e3
    ttft = np.asarray(rec.host["ttft_s"] or [0.0]) * 1e3
    itl = np.asarray(rec.host["itl_s"] or [0.0]) * 1e3
    print(f"window: {rec.window_s:.3f} s, {i} requests due, {len(done)} "
          f"finished, {rec.host['tokens']} tokens, "
          f"{len(rec.host['ticks'])} ticks, {len(rec.host['prefills'])} "
          f"admissions, compiles {rec.compiles}; generator late p50 "
          f"{np.median(late):.2f} max {late.max():.2f} ms; TTFT n "
          f"{len(rec.host['ttft_s'])} p50/p75/p90 "
          f"{np.percentile(ttft, 50):.1f}/{np.percentile(ttft, 75):.1f}/"
          f"{np.percentile(ttft, 90):.1f} ms; ITL n {len(rec.host['itl_s'])}"
          f" p50/p95 {np.percentile(itl, 50):.1f}/"
          f"{np.percentile(itl, 95):.1f} ms; host CPU user/system "
          f"{ru1.ru_utime - ru0.ru_utime:.2f}/"
          f"{ru1.ru_stime - ru0.ru_stime:.2f} s", file=sys.stderr)
    return rec


def _hooks_fired(rec: Record, admitted: int) -> None:
    """The wrapped calls ran once for every admission and every tick; a
    server that admits or decodes by another path would leave the spans
    and the counts behind ``mfu`` and the kernel's roofline short."""
    prefills, decodes = len(rec.host["prefills"]), len(
        rec.host["decode_lengths"])
    ticks = len(rec.host["ticks"])
    if prefills != admitted or decodes != ticks or not ticks:
        raise RuntimeError(
            f"the server's hooks missed work: {prefills} prefill calls for "
            f"{admitted} admissions, {decodes} decode calls for {ticks} "
            "ticks (PagedServer._prefill_pages / _decode_via_tables)")


def _expected_tokens(prompt_len: int, max_new: int, cache_len: int) -> int:
    # the server retires a request at max_new tokens, or once its next
    # position would reach the last cache slot
    return min(max_new, cache_len - prompt_len)


def sample(outs, seed: int, settings: dict):
    """The finished requests the check compares, drawn from the seed: the
    longest always, then others until the token or request count is
    reached.  Returns ``(prompt, served tokens)`` pairs."""
    rng = np.random.default_rng([seed, 2])
    order = sorted(range(len(outs)), key=lambda j: -len(outs[j][1]))[:1]
    order += [j for j in rng.permutation(len(outs)).tolist()
              if j not in order]
    seqs, served = [], 0
    for j in order:
        if len(seqs) == int(settings["check_requests"]) or served >= int(
                settings["check_tokens"]):
            break
        seqs.append(tuple(outs[j][:2]))
        served += len(outs[j][1])
    return seqs


def check(state: State, rec: Record, lowp=None):
    """Sample finished requests (the longest always among them), run the
    float32 reference over each prompt and its served tokens, and report
    the widest gap by which a served token's logit lies below the
    reference's best, relative to the row's largest logit.

    With ``lowp`` (a dtype name), the control is put in the program's
    place as well: the tokens that the reference computed with ``lowp``
    matmul inputs puts first, at the same positions.  Returns the
    program's checks and the control's, judged alike."""
    from bench.run import load_module, BENCH_DIR

    settings, cfg = state.cell["settings"], state.cell["config"]
    cache_len = int(settings["cache_len"])
    state.srv = state.params = None  # free the program's device state
    gc.collect()
    outs = rec.outputs
    bad = sum(len(out) != _expected_tokens(len(p), m, cache_len)
              for p, out, m in outs)
    seqs = sample(outs, state.seed, settings)
    served = sum(len(out) for _, out in seqs)
    ref = load_module(
        os.path.join(BENCH_DIR, "reference", cfg["reference"] + ".py"),
        "bench_reference_" + cfg["reference"])
    gaps = [float("inf")] * (2 if lowp else 1)
    if seqs:
        g = ref.gaps(cfg, state.seed, seqs, cache_len,
                     int(settings["check_requests"]), lowp=lowp)
        gaps = [float(row.max()) for row in g]
    print(f"check: {len(seqs)} requests, {served} served tokens compared",
          file=sys.stderr)
    checks = [{
        "logit_gap": {"value": gap, "limit": settings["logit_gap_limit"]},
        "token_count_errors": {"value": bad, "limit": 0},
        "tokens_short": {
            "value": max(0, int(settings["check_tokens"]) - served),
            "limit": 0},
    } for gap in gaps]
    return checks[0] if lowp is None else tuple(checks)
