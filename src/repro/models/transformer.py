"""Generic layer-program stack: one assembler for all 10 architectures.

A model is (embedding) + a sequence of :class:`Segment` scans + (lm head).
Each segment scans over stacked per-layer parameters; the scan body applies
the segment's repeating unit of block kinds.  The same assembler therefore
builds llama (global×N), gemma3 (5 local + 1 global), arctic/kimi (MoE),
falcon-mamba (mamba×N), recurrentgemma (rec,rec,local), llama-vision
(4 self + 1 cross) and the seamless encoder/decoder stacks.

Modes: ``train`` (no caches, optional remat), ``prefill`` (returns caches),
``decode`` (one token, consumes/returns caches).  Cache pytrees carry the
scan-stacked leading dimension, so prefill outputs plug directly into
decode inputs — and their ShapeDtypeStructs are what the multi-pod dry-run
lowers ``serve_step`` against.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.models import layers as L
from repro.models.common import ArchConfig, Segment, build_layer_program
from repro.parallel.ctx import RunCtx, shard

Params = Dict[str, Any]


# --------------------------------------------------------------------------- #
# blocks
# --------------------------------------------------------------------------- #
def block_init(kind: str, cfg: ArchConfig, ctx: RunCtx, key) -> Tuple[Params, Params]:
    ks = jax.random.split(key, 4)
    if kind in ("global", "local", "dense", "enc"):
        d_ff = cfg.resolved_d_ff_dense if kind == "dense" else cfg.d_ff
        ap, asp = L.attention_init(cfg, ctx, ks[0])
        mp, msp = L.mlp_init(cfg, ctx, ks[1], d_ff=d_ff)
        return {"attn": ap, "mlp": mp}, {"attn": asp, "mlp": msp}
    if kind == "moe":
        ap, asp = L.attention_init(cfg, ctx, ks[0])
        mp, msp = L.moe_init(cfg, ctx, ks[1])
        return {"attn": ap, "moe": mp}, {"attn": asp, "moe": msp}
    if kind == "mamba":
        mp, msp = L.mamba_init(cfg, ctx, ks[0])
        return {"mix": mp}, {"mix": msp}
    if kind == "rec":
        rp, rsp = L.rec_init(cfg, ctx, ks[0])
        mp, msp = L.mlp_init(cfg, ctx, ks[1])
        return {"mix": rp, "mlp": mp}, {"mix": rsp, "mlp": msp}
    if kind in ("cross", "xdec"):
        ap, asp = L.attention_init(cfg, ctx, ks[0])
        xp, xsp = L.attention_init(cfg, ctx, ks[1])
        mp, msp = L.mlp_init(cfg, ctx, ks[2])
        params = {"attn": ap, "xattn": xp, "mlp": mp}
        specs = {"attn": asp, "xattn": xsp, "mlp": msp}
        if kind == "cross":
            params["xgate"] = jnp.zeros((), jnp.float32)
            specs["xgate"] = P()
        return params, specs
    raise ValueError(kind)


def block_apply(
    kind: str,
    p: Params,
    cfg: ArchConfig,
    ctx: RunCtx,
    x: jax.Array,
    *,
    mode: str,
    cache: Optional[Params],
    cache_len: int,
    positions: jax.Array,
    xkv: Optional[jax.Array],
    page_table: Optional[jax.Array] = None,
    layer: Optional[jax.Array] = None,
    tp=None,
) -> Tuple[jax.Array, Optional[Params]]:
    get = lambda k: None if cache is None else cache.get(k)
    new_cache: Dict[str, Any] = {}

    from jax.ad_checkpoint import checkpoint_name

    if kind in ("global", "local", "dense", "enc", "moe"):
        window = cfg.local_window if kind == "local" else None
        causal = kind != "enc"
        a, ac = L.apply_attention(
            p["attn"], cfg, ctx, x, positions=positions, causal=causal,
            window=window, mode=mode, cache=get("attn"), cache_len=cache_len,
            page_table=page_table, layer=layer, tp=tp,
        )
        x = x + checkpoint_name(a, "attn_out")
        if ac is not None:
            new_cache["attn"] = ac
        if kind == "moe":
            # MoE experts stay replicated inside a TP group (expert
            # parallelism is the model-axis story); no psum needed
            x = x + checkpoint_name(L.apply_moe(p["moe"], cfg, ctx, x),
                                    "moe_out")
        else:
            x = x + checkpoint_name(L.apply_mlp(p["mlp"], cfg, x, ctx, tp=tp),
                                    "mlp_out")
    elif kind == "mamba":
        if page_table is not None:
            raise ValueError("paged decode unsupported for 'mamba' blocks")
        m, mc = L.apply_mamba(p["mix"], cfg, ctx, x, mode=mode, cache=get("mix"))
        x = x + checkpoint_name(m, "mix_out")
        if mc is not None:
            new_cache["mix"] = mc
    elif kind == "rec":
        if page_table is not None:
            raise ValueError("paged decode unsupported for 'rec' blocks")
        m, mc = L.apply_rec(p["mix"], cfg, ctx, x, mode=mode, cache=get("mix"))
        x = x + checkpoint_name(m, "mix_out")
        if mc is not None:
            new_cache["mix"] = mc
        x = x + checkpoint_name(L.apply_mlp(p["mlp"], cfg, x, ctx, tp=tp),
                                "mlp_out")
    elif kind in ("cross", "xdec"):
        if page_table is not None:
            raise ValueError(f"paged decode unsupported for {kind!r} blocks")
        a, ac = L.apply_attention(
            p["attn"], cfg, ctx, x, positions=positions, causal=True,
            mode=mode, cache=get("attn"), cache_len=cache_len, tp=tp,
        )
        x = x + a
        if ac is not None:
            new_cache["attn"] = ac
        c, cc = L.apply_attention(
            p["xattn"], cfg, ctx, x, positions=positions, mode=mode,
            cache=get("xattn"), cache_len=cache_len, xkv=xkv, tp=tp,
        )
        if kind == "cross":
            c = jnp.tanh(p["xgate"]).astype(c.dtype) * c
        x = x + c
        if cc is not None:
            new_cache["xattn"] = cc
        x = x + L.apply_mlp(p["mlp"], cfg, x, ctx, tp=tp)
    else:
        raise ValueError(kind)
    x = shard(x, ctx, ctx.hidden_spec())
    return x, (new_cache if new_cache else None)


# --------------------------------------------------------------------------- #
# stacks (segment scans)
# --------------------------------------------------------------------------- #
def stack_init(
    kinds: Sequence[str], cfg: ArchConfig, ctx: RunCtx, key
) -> Tuple[List[Segment], List[Params], List[Params]]:
    segments = build_layer_program(kinds)
    seg_params: List[Params] = []
    seg_specs: List[Params] = []
    for si, seg in enumerate(segments):
        def unit_init(k):
            ks = jax.random.split(k, len(seg.unit))
            pd, sd = {}, {}
            for i, kind in enumerate(seg.unit):
                pd[f"b{i}_{kind}"], sd[f"b{i}_{kind}"] = block_init(
                    kind, cfg, ctx, ks[i]
                )
            return pd, sd

        keys = jax.random.split(jax.random.fold_in(key, si), seg.count)
        _, sspec = unit_init(keys[0])
        stacked = jax.vmap(lambda k: unit_init(k)[0])(keys)
        seg_params.append(stacked)
        seg_specs.append(
            jax.tree.map(
                lambda s: P(*((None,) + tuple(s))),
                sspec,
                is_leaf=lambda s: isinstance(s, P),
            )
        )
    return segments, seg_params, seg_specs


def _maybe_remat(fn: Callable, ctx: RunCtx, mode: str) -> Callable:
    if mode != "train" or ctx.remat == "none":
        return fn
    if ctx.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    if ctx.remat == "names":
        # §Perf iteration: save exactly the post-collective sub-block
        # outputs.  The backward pass then never re-runs the tensor-parallel
        # all-reduces that full remat duplicates (the dominant collective
        # cost measured in the baseline), at the price of two extra saved
        # (B, S, D) tensors per layer (shard them with seq_shard_acts).
        return jax.checkpoint(
            fn,
            policy=jax.checkpoint_policies.save_only_these_names(
                "attn_out", "mlp_out", "moe_out", "mix_out"
            ),
        )
    return jax.checkpoint(fn)


def stack_apply(
    segments: List[Segment],
    seg_params: List[Params],
    cfg: ArchConfig,
    ctx: RunCtx,
    x: jax.Array,
    *,
    mode: str,
    caches: Optional[List[Any]] = None,
    cache_len: int = 0,
    positions: jax.Array,
    xkv: Optional[jax.Array] = None,
    page_table: Optional[jax.Array] = None,
    tp=None,
) -> Tuple[jax.Array, Optional[List[Any]]]:
    new_caches: List[Any] = []
    for si, (seg, sp) in enumerate(zip(segments, seg_params)):
        sc = caches[si] if caches is not None else None

        def unit_body(xc, lp, lc, layer=None):
            ncs = {}
            for i, kind in enumerate(seg.unit):
                key = f"b{i}_{kind}"
                xc, nc = block_apply(
                    kind, lp[key], cfg, ctx, xc, mode=mode,
                    cache=None if lc is None else lc[key],
                    cache_len=cache_len, positions=positions, xkv=xkv,
                    page_table=page_table, layer=layer, tp=tp,
                )
                if nc is not None:
                    ncs[key] = nc
            return xc, ncs

        if mode == "train":
            body = _maybe_remat(
                lambda xc, lp: (unit_body(xc, lp, None)[0], None), ctx, mode
            )
            x, _ = lax.scan(body, x, sp)
            new_caches.append(None)
        elif mode == "prefill":
            def body_p(xc, lp):
                return unit_body(xc, lp, None)

            x, ncs = lax.scan(body_p, x, sp)
            new_caches.append(ncs)
        elif mode == "decode" and page_table is not None:
            # paged decode: the (L, P, T, ...) page pools ride the scan's
            # carry, and each layer scatters its token at [layer, page,
            # slot] and reads its layer in the kernel.  Pools as xs/ys
            # would cut every layer's pool out and write it to a fresh
            # stack; carried, the donated pool is updated in place.
            def body_pd(carry, lp_l):
                (xc, pools), (lp, layer) = carry, lp_l
                return unit_body(xc, lp, pools, layer), None

            (x, ncs), _ = lax.scan(
                body_pd, (x, sc), (sp, jnp.arange(seg.count, dtype=jnp.int32))
            )
            new_caches.append(ncs)
        elif mode == "decode":
            def body_d(xc, lp_lc):
                lp, lc = lp_lc
                xc, ncs = unit_body(xc, lp, lc)
                return xc, ncs

            x, ncs = lax.scan(body_d, x, (sp, sc))
            new_caches.append(ncs)
        else:
            raise ValueError(mode)
    return x, (new_caches if mode != "train" else None)


# --------------------------------------------------------------------------- #
# embedding + head + loss
# --------------------------------------------------------------------------- #
def lm_io_init(cfg: ArchConfig, ctx: RunCtx, key) -> Tuple[Params, Params]:
    ks = jax.random.split(key, 2)
    params = {
        "tok": L._normal(ks[0], (cfg.vocab, cfg.d_model), cfg.dtype, 0.02),
        "norm_f": L.norm_init(cfg.d_model),
    }
    specs = {"tok": P(ctx.tp, "data"), "norm_f": L.norm_specs()}
    if not cfg.tie_embeddings:
        params["out"] = L.linear_init(ks[1], cfg.d_model, (cfg.vocab,), cfg.dtype)
        specs["out"] = P("data", ctx.tp)
    return params, specs


def embed(io: Params, cfg: ArchConfig, ctx: RunCtx, tokens: jax.Array) -> jax.Array:
    x = jnp.take(io["tok"], tokens, axis=0)
    return shard(x, ctx, ctx.hidden_spec())


def _proj_logits(io: Params, cfg: ArchConfig, h: jax.Array,
                 ctx: RunCtx = None) -> jax.Array:
    from repro.parallel.ctx import use_weight

    ctx = ctx or RunCtx(mesh=None)
    if cfg.tie_embeddings:
        tok = use_weight(io["tok"], ctx, P(ctx.tp, None))
        return h @ tok.T
    out = use_weight(io["out"], ctx, P(None, ctx.tp))
    return h @ out


def final_hidden(io: Params, cfg: ArchConfig, h: jax.Array) -> jax.Array:
    return L.apply_norm(io["norm_f"], h, cfg.norm)


def logits_fn(io: Params, cfg: ArchConfig, ctx: RunCtx, h: jax.Array) -> jax.Array:
    out = _proj_logits(io, cfg, final_hidden(io, cfg, h), ctx)
    return shard(out, ctx, P(ctx.dp, None, ctx.tp))


def chunked_ce_loss(
    io: Params,
    cfg: ArchConfig,
    ctx: RunCtx,
    h: jax.Array,
    targets: jax.Array,
    mask: jax.Array,
    chunk: int = 512,
) -> jax.Array:
    """Cross-entropy without materializing the full (B, S, V) logits.

    Scans over sequence chunks; each chunk projects to the vocabulary,
    reduces, and is discarded — peak logits memory drops from O(S·V) to
    O(chunk·V) per batch row (decisive for the 262k/256k vocab archs).
    """
    B, S, D = h.shape
    h = final_hidden(io, cfg, h)
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    nc = h.shape[1] // chunk

    def body(acc, ci):
        hs = lax.dynamic_slice_in_dim(h, ci * chunk, chunk, axis=1)
        ts = lax.dynamic_slice_in_dim(targets, ci * chunk, chunk, axis=1)
        ms = lax.dynamic_slice_in_dim(mask, ci * chunk, chunk, axis=1)
        logits = _proj_logits(io, cfg, hs, ctx).astype(jnp.float32)
        logits = shard(logits, ctx, P(ctx.dp, None, ctx.tp))
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, ts[..., None], axis=-1)[..., 0]
        nll = (lse - tgt) * ms
        return (acc[0] + nll.sum(), acc[1] + ms.sum()), None

    (tot, cnt), _ = lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        jnp.arange(nc),
    )
    return tot / jnp.maximum(cnt, 1.0)
