"""Smoke run of the serving path and the GAS layer on TPU chips.

Default (one chip): qwen3-4b at its published widths (36 layers,
``d_model`` 2560, vocab 151936, bf16; random weights from ``--seed``) is
served by ``repro.launch.serve.PagedServer`` with the Pallas paged
attention kernel compiled by Mosaic.  The same requests are served again
with the jnp oracle (``attn_impl="chunked"``), teacher-forced on the
kernel server's tokens, so the two are compared at every decode step,
pages grown during decode included: the logits must agree within
``LOGIT_RTOL`` of the largest, and where the oracle would pick another
token than the kernel did, the two must be a near tie (within
``TIE_RTOL``), which rounding may decide either way.  A third run, the
oracle at "highest" matmul precision, is printed as the witness of how
far rounding alone moves this model's logits.

``--four-chips``: the GAS layer's bitwise engine parity (``xla``,
compiled ``gascore`` and the mixed map) across four chips, then a
``DisaggCluster`` of 1 prefill + 3 decode ranks, one chip each, whose
tokens must match a one-chip ``PagedServer`` serving the same requests.
The model there is qwen3-4b at published widths cut to ``FOUR_CHIP_LAYERS``
layers: the cluster's host-managed pool segments are re-sent every tick,
and a full-depth segment would turn each tick into gigabytes of traffic.

The script fails (non-zero exit, no result line) without a TPU, without
the repository's ``src`` beside it, or on any mismatch.  The last line of
a passing run is ``{"ok": true, "device": {...}}``.  Rates printed on the
way are from a smoke run, not a benchmark.

    python3 chip_smoke.py [--four-chips] [--seed N]
"""
import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

ARCH = "qwen3-4b"
BATCH = 8
CACHE_LEN = 2048
PAGE_TOKENS = 16
PROMPT_LENS = (128, 200, 320, 512)  # 200: a prompt ending mid-page
MAX_NEW = 32
FOUR_CHIP_LAYERS = 4
FOUR_CHIP_CACHE_LEN = 576  # longest prompt + MAX_NEW, in whole pages
# bf16 keeps 8 significant bits: one rounding moves a value by up to
# 2**-9 of itself.  The kernel and the oracle round differently inside
# each of the 36 layers' attention; 2**-5 of the largest logit bounds the
# accumulated difference with room, and is far below the gap a wrong
# kernel (a dropped page, a wrong head, a missing mask) opens.
LOGIT_RTOL = 2.0**-5
# two logits that each move by up to LOGIT_RTOL of the largest can swap
# order only when they were within twice that of each other
TIE_RTOL = 2 * LOGIT_RTOL
# one attention call, kernel against a float32 oracle at "highest" matmul
# precision: the bf16 output allows 2**-8 of its largest value per
# rounding; 2**-6 leaves room for the kernel's own roundings
KERNEL_RTOL = 2.0**-6


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def requests(vocab: int, seed: int, n: int = BATCH):
    from repro.launch.serve import Request

    rng = np.random.default_rng(seed)
    return [
        Request(
            rid=i,
            prompt=rng.integers(0, vocab, PROMPT_LENS[i % len(PROMPT_LENS)])
            .tolist(),
            max_new=MAX_NEW,
        )
        for i in range(n)
    ]


def recording_server(*args, script=None, **kw):
    """A ``PagedServer`` that keeps, per request, the logits of every
    decode step (float32 rows, in step order).

    With ``script`` (rid -> token list) it is teacher-forced: the prefill
    token and every decoded token are taken from the script instead of
    its own argmax, so two servers can be compared step by step on the
    same token history."""
    from repro.launch.serve import PagedServer

    class Recording(PagedServer):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            self.steps = {}
            self.own_first = {}  # rid -> the token its own prefill chose

        def _prefill_pages(self, req):
            tok, caches = super()._prefill_pages(req)
            self.own_first[req.rid] = tok
            if script is not None:
                tok = script[req.rid][0]
            return tok, caches

        def _decode_via_tables(self, tables):
            logits = super()._decode_via_tables(tables)
            if script is not None:
                logits = logits.copy()
            for i, req in enumerate(self.active):
                if req is None:
                    continue
                self.steps.setdefault(req.rid, []).append(
                    logits[i].astype(np.float32)
                )
                if script is not None:
                    logits[i] = 0
                    logits[i, script[req.rid][len(req.out)]] = 1
            return logits

    return Recording(*args, **kw)


def serve(model, ctx, params, reqs, cache_len: int, n_pool_pages=None,
          script=None):
    """Serve ``reqs`` to completion; returns the server and timings."""
    srv = recording_server(
        model, ctx, params, BATCH, cache_len, page_tokens=PAGE_TOKENS,
        n_pool_pages=n_pool_pages, script=script,
    )
    for r in reqs:
        srv.submit(r)
    t0 = time.monotonic()
    srv.step()  # admits every request: prefills + compiles
    t_first = time.monotonic() - t0
    t1 = time.monotonic()
    ticks = decoded = 0
    while srv._pending():
        decoded += srv.step()
        ticks += 1
    t_rest = time.monotonic() - t1
    stats = srv.run_until_drained()  # nothing left: syncs the pool, stats
    if stats.get("sched_evictions", 0):
        fail("the pool preempted a request; the smoke sizes must not")
    return srv, {
        "first_tick_s": t_first, "ticks": ticks, "decode_s": t_rest,
        "decoded": decoded,
    }


def tokens_of(srv) -> dict:
    return {r.rid: list(r.out) for r in srv.finished}


def compare_tokens(name, got, ref_srv, reqs) -> int:
    """Tokens of ``got`` (rid -> token list) against the recording server
    ``ref_srv`` running free; returns how many requests diverged at a
    near tie (later tokens of such a request are not compared)."""
    ties = 0
    ref = tokens_of(ref_srv)
    for r in reqs:
        a, b = got[r.rid], ref[r.rid]
        if len(a) != len(b):
            fail(f"{name}: rid {r.rid} made {len(a)} tokens, oracle {len(b)}")
        for j, (x, y) in enumerate(zip(a, b)):
            if x == y:
                continue
            if j == 0:
                fail(f"{name}: rid {r.rid} first token {x} != {y} "
                     f"(prefill is shared)")
            row = ref_srv.steps[r.rid][j - 1]
            margin = float(row.max() - row[x])
            scale = float(np.abs(row).max())
            if margin > TIE_RTOL * scale:
                fail(f"{name}: rid {r.rid} token {j}: {x} != {y}, margin "
                     f"{margin:.4g} > {TIE_RTOL} * {scale:.4g}")
            print(f"{name}: rid {r.rid} diverged at token {j} on a near tie "
                  f"(margin {margin:.4g}); later tokens not compared")
            ties += 1
            break
    return ties


def step_gaps(a: dict, b: dict) -> np.ndarray:
    """Per request and decode step, max |a - b| / max |b| of two
    recording servers' logits (``steps``) on the same token history."""
    if sorted(a) != sorted(b):
        fail(f"recorded requests differ: {sorted(a)} vs {sorted(b)}")
    return np.array([
        [float(np.abs(x - y).max()) / float(np.abs(y).max())
         for x, y in zip(a[rid], b[rid], strict=True)]
        for rid in sorted(b)
    ])


def build(cfg, seed: int):
    import jax

    from repro.models.build import build_model
    from repro.parallel.ctx import RunCtx

    model = build_model(cfg)
    ctx = RunCtx(mesh=None, remat="none")
    t0 = time.monotonic()
    params = jax.jit(lambda k: model.init(ctx, k)[0])(
        jax.random.PRNGKey(seed)
    )
    jax.block_until_ready(params)
    print(f"init: {time.monotonic() - t0:.1f} s")
    return model, ctx, params


def kernel_check(cfg, seed: int) -> None:
    """The compiled paged-attention kernel against the oracle on one
    call at the configuration's decode shapes: bf16 query and pages,
    requests of different lengths (one ending mid-page, one of a single
    position), and every table entry past a request's length pointing
    at a page full of NaN, which masking must keep out."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    width = CACHE_LEN // PAGE_TOKENS // 4  # 512 positions per request
    n_pages = BATCH * width + 1
    nan_page = n_pages - 1
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (n_pages, PAGE_TOKENS, cfg.n_kv_heads, cfg.resolved_head_dim)
    k = jax.random.normal(kk, shape, jnp.bfloat16).at[nan_page].set(jnp.nan)
    v = jax.random.normal(kv, shape, jnp.bfloat16).at[nan_page].set(jnp.nan)
    q = jax.random.normal(
        kq, (BATCH, cfg.n_heads, cfg.resolved_head_dim), jnp.bfloat16
    )
    lengths = np.array([512, 200, 1, 320, 128, 17, 480, 64], np.int32)
    rng = np.random.default_rng(seed)
    table = rng.permutation(BATCH * width).reshape(BATCH, width)
    for b, n in enumerate(lengths):
        table[b, -(-n // PAGE_TOKENS):] = nan_page
    args = (q, k, v, jnp.asarray(table, jnp.int32), jnp.asarray(lengths))
    got = np.asarray(ops.paged_attention(*args, impl="pallas"), np.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ops.paged_attention(*args, impl="ref"), np.float32)
    if not np.all(np.isfinite(got)):
        fail("paged attention kernel: non-finite output (masking)")
    gap = float(np.abs(got - want).max()) / float(np.abs(want).max())
    if gap > KERNEL_RTOL:
        fail(f"paged attention kernel vs oracle: {gap:.3e} > {KERNEL_RTOL}")
    print(f"paged attention kernel vs float32 oracle, one call: max |diff| "
          f"/ max |oracle| = {gap:.3e} (limit {KERNEL_RTOL:.3e})")


def phase_one_chip(cfg, seed: int, cache_len: int = CACHE_LEN) -> None:
    """Serve with the compiled kernel, then teacher-force the oracle, and
    the oracle at "highest" matmul precision, on the kernel's tokens; the
    kernel's logits must match the oracle's at every decode step.  The
    second oracle is the witness of how far rounding alone moves the
    logits of this model."""
    import jax

    from repro.kernels import mode

    model, ctx, params = build(cfg, seed)
    n_pool_pages = BATCH * (cache_len // PAGE_TOKENS)
    impl = mode.decode_attn_impl(ctx.attn_impl)
    if impl != "pallas" or not mode.on_tpu():
        fail(f"decode attention is {impl!r} on {mode.platform()}")
    print(f"model: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"vocab={cfg.vocab} dtype={np.dtype(cfg.dtype).name}; pool "
          f"{n_pool_pages} pages x {PAGE_TOKENS} tokens")
    kernel_check(cfg, seed)
    reqs = requests(cfg.vocab, seed)
    kern, t_k = serve(model, ctx, params, reqs, cache_len, n_pool_pages)
    print(f"pallas decode (compiled): first tick {t_k['first_tick_s']:.1f} s "
          f"(8 prefills + compiles), {t_k['ticks']} more ticks in "
          f"{t_k['decode_s']:.2f} s, {t_k['decoded'] / t_k['decode_s']:.1f} "
          f"tok/s (smoke, not a benchmark)")
    script = tokens_of(kern)
    k_steps = kern.steps
    del kern
    oracle_ctx = dataclasses.replace(ctx, attn_impl="chunked")
    ref, t_r = serve(model, oracle_ctx, params, requests(cfg.vocab, seed),
                     cache_len, n_pool_pages, script=script)
    print(f"oracle decode on the kernel's tokens: first tick "
          f"{t_r['first_tick_s']:.1f} s")
    o_first, o_steps = ref.own_first, ref.steps
    del ref
    with jax.default_matmul_precision("highest"):
        wit, _ = serve(model, oracle_ctx, params, requests(cfg.vocab, seed),
                       cache_len, n_pool_pages, script=script)
    h_first, h_steps = wit.own_first, wit.steps
    del wit

    for rid, row in k_steps.items():
        if not all(np.all(np.isfinite(x)) for x in row):
            fail(f"rid {rid}: non-finite logits from the kernel")
    ko = step_gaps(k_steps, o_steps)
    oh = step_gaps(o_steps, h_steps)
    kh = step_gaps(k_steps, h_steps)
    rid, step = np.unravel_index(int(ko.argmax()), ko.shape)
    print(f"logits over {ko.size} decode steps ({len(reqs)} requests x "
          f"{ko.shape[1]}), max |a - b| / max |b|: pallas vs oracle "
          f"{ko.max():.3e} (step {step} of rid {rid}; first step "
          f"{ko[:, 0].max():.3e}; limit {LOGIT_RTOL:.3e})")
    print(f"rounding witness, oracle vs oracle at 'highest' precision: "
          f"{oh.max():.3e} (first step {oh[:, 0].max():.3e}); pallas vs "
          f"oracle at 'highest': {kh.max():.3e}")
    same_o = sum(o_first[r] == script[r][0] for r in script)
    same_h = sum(h_first[r] == script[r][0] for r in script)
    print(f"prefill tokens equal to the kernel server's: oracle "
          f"{same_o}/{len(script)}, 'highest' {same_h}/{len(script)}")
    # with the logits within the limit, the oracle can prefer another
    # token only where its top logit and the kernel's token lie within
    # twice the limit of each other: a near tie
    ties = 0
    for r, rows in o_steps.items():
        for j, row in enumerate(rows):
            tok = script[r][j + 1]
            margin = float(row.max() - row[tok])
            if margin > 0:
                ties += 1
            if margin > TIE_RTOL * float(np.abs(row).max()):
                fail(f"rid {r} step {j}: the kernel chose {tok}, "
                     f"{margin:.4g} below the oracle's top logit")
    print(f"tokens: the oracle's argmax differs from the kernel's token at "
          f"{ties} of {ko.size} steps, each a near tie")
    if any(o_first[r] != script[r][0] for r in script):
        fail("the oracle's prefill chose other first tokens (prefill is "
             "shared)")
    if ko.max() > LOGIT_RTOL:
        fail(f"rid {rid} step {step}: logits differ by {ko.max():.4g} of "
             f"the largest > {LOGIT_RTOL}")
    stats = jax.devices()[0].memory_stats() or {}
    print(f"requests: {len(reqs)} per server; peak device bytes: "
          f"{stats.get('peak_bytes_in_use', 'not reported')}")


def phase_four_chips(cfg, seed: int, cache_len: int) -> None:
    import jax

    from repro.serving.disagg import DisaggCluster
    from repro.testing import gascore_suite

    t0 = time.monotonic()
    gascore_suite.engine_parity(4)
    print(f"GAS engine parity (xla / gascore / xla,gascore), bitwise: OK "
          f"({time.monotonic() - t0:.1f} s incl. compiles)")

    model, ctx, params = build(cfg, seed)
    reqs = requests(cfg.vocab, seed)
    one, _ = serve(model, ctx, params, reqs, cache_len)
    cluster = DisaggCluster(
        model, ctx, params, n_prefill=1, n_decode=3, decode_batch=4,
        cache_len=cache_len, paged=True, page_tokens=PAGE_TOKENS,
    )
    ranks = list(cluster.mesh.devices.flat)
    homes = {
        next(iter(jax.tree_util.tree_leaves(s.params)[0].devices()))
        for s in cluster.decode_servers
    }
    if len(set(ranks)) != 4 or homes != set(ranks[1:]):
        fail(f"cluster ranks on {ranks}, decode weights on {homes}")
    # the transfer program's segment must come back sharded over all four
    seg_devs = set()
    consume = cluster._consume_transfer

    def watched(results):
        seg_devs.update(results[0].sharding.device_set)
        return consume(results)

    cluster._consume_transfer = watched
    reqs = requests(cfg.vocab, seed)
    for r in reqs:
        cluster.submit(r)
    stats = cluster.run_until_drained()
    if seg_devs != set(ranks):
        fail(f"pool segments on {seg_devs}, ranks on {ranks}")
    print(f"disagg 1 prefill + 3 decode: one rank per chip "
          f"({[d.id for d in ranks]}), segments on {len(seg_devs)} devices, "
          f"{stats['kv_transfers']} KV transfers, {stats['ticks']} ticks")
    got = {r.rid: list(r.out) for r in cluster.finished}
    if len(got) != len(reqs):
        fail(f"cluster finished {len(got)} of {len(reqs)} requests")
    ties = compare_tokens("disagg vs one chip", got, one, reqs)
    print(f"disagg tokens: {len(reqs) - ties}/{len(reqs)} requests identical "
          f"to the one-chip server, {ties} near-tie divergence(s)")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="GAS parity and disaggregated serving on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        fail(f"no repository source at {src}")
    sys.path.insert(0, src)

    import jax

    from repro.configs.registry import ARCHS
    from repro.launch import compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX found {dev.platform} devices")
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        fail(f"need {want} TPU chips, found {len(devices)}")
    print(f"device_kind: {dev.device_kind}, devices: {len(devices)}, "
          f"compile cache: {compile_cache.enable()}")

    cfg = ARCHS[ARCH]
    if args.four_chips:
        if len(devices) != 4:
            fail(f"--four-chips needs exactly 4 chips, found {len(devices)}")
        cut = dataclasses.replace(cfg, n_layers=FOUR_CHIP_LAYERS)
        phase_four_chips(cut, args.seed, FOUR_CHIP_CACHE_LEN)
    else:
        phase_one_chip(cfg, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))


if __name__ == "__main__":
    main()
