"""Composable model layers (pure-functional, params-as-pytrees).

Every ``*_init`` returns ``(params, specs)`` — two trees of identical
structure, the second holding ``PartitionSpec`` leaves.  Sharding therefore
travels with the parameters (FSDP over ``data``, tensor parallel over
``model``), and stacking layers for scan simply prepends ``None``.

Activations receive explicit constraints only at block boundaries; XLA's
sharding propagation handles the interior from the parameter specs.

The MoE layer has two execution paths (the paper's software/hardware story
at the *parallelism* level):

- ``local``        — single-device reference (smoke tests, examples).
- ``ep_shardmap``  — expert parallelism via an explicit Active-Message-style
  dispatch: tokens are routed into capacity-bounded per-expert buffers
  (``kernels.moe_router`` semantics), exchanged with an all-to-all over the
  ``model`` axis — through the GAS engine, so the transport can be the XLA
  software path or the GAScore ring — computed by the expert's home device,
  and combined back.  Expert-weight gradients reduce over ``data``
  automatically via the shard_map transpose.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from jax import shard_map

from repro.kernels import mode as kmode
from repro.kernels import ops
from repro.models.common import ArchConfig
from repro.parallel.ctx import RunCtx, use_weight

Params = Dict[str, Any]


# --------------------------------------------------------------------------- #
# initializers
# --------------------------------------------------------------------------- #
def _normal(key, shape, dtype, scale):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def linear_init(key, in_dim: int, out_dims, dtype, scale=None):
    shape = (in_dim,) + tuple(out_dims if isinstance(out_dims, tuple) else (out_dims,))
    scale = scale if scale is not None else 1.0 / math.sqrt(in_dim)
    return _normal(key, shape, dtype, scale)


def norm_init(d: int) -> Params:
    return {"scale": jnp.ones((d,), jnp.float32)}


def norm_specs() -> Params:
    return {"scale": P(None)}


def apply_norm(p: Params, x: jax.Array, kind: str = "rmsnorm", eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(var + eps) * p["scale"]
    else:  # layernorm (no bias)
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"]
    return out.astype(x.dtype)


def _act(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[name]


# --------------------------------------------------------------------------- #
# RoPE
# --------------------------------------------------------------------------- #
def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, Dh); positions: (B, S) int32."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = jnp.exp(
        -math.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half
    )
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# --------------------------------------------------------------------------- #
# attention
# --------------------------------------------------------------------------- #
def _kv_spec(cfg: ArchConfig, ctx: RunCtx) -> P:
    """KV projections: heads over tp when divisible, else replicated heads."""
    if ctx.tp_size and cfg.n_kv_heads % ctx.tp_size == 0:
        return P("data", ctx.tp, None)
    return P("data", None, None)


def attention_init(cfg: ArchConfig, ctx: RunCtx, key) -> Tuple[Params, Params]:
    dh = cfg.resolved_head_dim
    D, H, KH = cfg.d_model, cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 6)
    params = {
        "norm": norm_init(D),
        "wq": linear_init(ks[0], D, (H, dh), cfg.dtype),
        "wk": linear_init(ks[1], D, (KH, dh), cfg.dtype),
        "wv": linear_init(ks[2], D, (KH, dh), cfg.dtype),
        "wo": linear_init(ks[3], H * dh, (D,), cfg.dtype),
    }
    specs = {
        "norm": norm_specs(),
        "wq": P("data", ctx.tp, None),
        "wk": _kv_spec(cfg, ctx),
        "wv": _kv_spec(cfg, ctx),
        "wo": P(ctx.tp, "data"),
    }
    if cfg.qk_norm:
        params["q_norm"] = norm_init(dh)
        params["k_norm"] = norm_init(dh)
        specs["q_norm"] = norm_specs()
        specs["k_norm"] = norm_specs()
    return params, specs


def _gqa_scores_softmax_v(q, k, v, mask, scale):
    """q: (B,Sq,H,Dh), k/v: (B,Sk,KH,Dh), mask: (B,Sq,Sk) bool."""
    B, Sq, H, Dh = q.shape
    KH = k.shape[2]
    G = H // KH
    qg = q.reshape(B, Sq, KH, G, Dh)
    # keep activations in model dtype; accumulate the dots in f32
    # (preferred_element_type) so backward cotangents stay bf16 — the f32
    # cotangent all-reduces were a measured 1e12 B/device in the llama
    # baseline (§Perf iteration D).
    s = jnp.einsum(
        "bqkgd,bskd->bkgqs", qg * jnp.asarray(scale, q.dtype), k,
        preferred_element_type=jnp.float32,
    )  # (B, KH, G, Sq, Sk) f32
    s = jnp.where(mask[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum(
        "bkgqs,bskd->bqkgd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    visible = mask.any(axis=-1)  # (B, Sq)
    o = jnp.where(visible[:, :, None, None, None], o, 0.0)
    return o.reshape(B, Sq, H, Dh).astype(q.dtype)


def _chunked_attention(q, k, v, qpos, kpos, *, causal, window, scale, chunk):
    """Blockwise-over-queries attention (jnp; differentiable; O(S·chunk) mem).

    qpos: (B, Sq) absolute query positions; kpos: (B, Sk) key positions
    (-1 = empty cache slot).
    """
    B, Sq, H, Dh = q.shape
    chunk = min(chunk, Sq)
    pad = (-Sq) % chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        qpos = jnp.pad(qpos, ((0, 0), (0, pad)), constant_values=-1)
    nq = q.shape[1] // chunk

    def one_chunk(ci):
        qs = lax.dynamic_slice_in_dim(q, ci * chunk, chunk, axis=1)
        qp = lax.dynamic_slice_in_dim(qpos, ci * chunk, chunk, axis=1)
        mask = kpos[:, None, :] >= 0
        if causal:
            mask &= qp[:, :, None] >= kpos[:, None, :]
        if window is not None:
            mask &= (qp[:, :, None] - kpos[:, None, :]) < window
            if not causal:
                mask &= (kpos[:, None, :] - qp[:, :, None]) < window
        mask &= qp[:, :, None] >= 0
        return _gqa_scores_softmax_v(qs, k, v, mask, scale)

    outs = lax.map(one_chunk, jnp.arange(nq))  # (nq, B, chunk, H, Dh)
    out = jnp.moveaxis(outs, 0, 1).reshape(B, nq * chunk, H, Dh)
    return out[:, :Sq]


def apply_attention(
    p: Params,
    cfg: ArchConfig,
    ctx: RunCtx,
    x: jax.Array,
    *,
    positions: jax.Array,
    causal: bool = True,
    window: Optional[int] = None,
    mode: str = "train",
    cache: Optional[Params] = None,
    cache_len: int = 0,
    xkv: Optional[jax.Array] = None,
    page_table: Optional[jax.Array] = None,
    tp=None,
) -> Tuple[jax.Array, Optional[Params]]:
    """Self- or cross-attention sub-block (pre-norm, residual added by caller).

    Modes:
      train    — full-sequence self-attention, no cache.
      prefill  — full sequence; returns a cache of capacity ``cache_len``.
      decode   — x is (B, 1, D); reads/updates ``cache``.
    Cross-attention (``xkv`` given): keys/values come from ``xkv``
    (B, S_enc, D); cache (mode != train) stores the projected enc KV.

    Paged decode (``page_table`` given, decode mode only): ``cache`` holds
    the layer's slice of the global KV *page pool* — ``k``/``v`` shaped
    ``(P, page_tokens, KH, Dh)`` and ``pos`` ``(P, page_tokens)`` — and
    ``page_table`` is ``(B, NP)`` physical ids per request.  The new
    token's K/V scatter straight into the request's (COW-resolved,
    materialised) page and attention runs through the table via
    ``kernels.paged_attention`` — no dense per-request rows anywhere.

    Tensor-parallel (``tp`` a :class:`~repro.parallel.tp.TPGroup`): the
    caller passes head-sharded ``wq/wk/wv/wo`` — every per-head
    computation (projections, rope, softmax, the paged pool writes) is
    rank-local and identical to the matching head slice of the
    unsharded run; only the output projection's partial sum crosses the
    group, via ``tp.psum``.
    """
    dh = cfg.resolved_head_dim
    scale = 1.0 / math.sqrt(dh)
    B, S, D = x.shape
    h = apply_norm(p["norm"], x, cfg.norm)
    wq = use_weight(p["wq"], ctx, P(None, ctx.tp, None))
    wk = use_weight(p["wk"], ctx, P(None, ctx.tp, None))
    wv = use_weight(p["wv"], ctx, P(None, ctx.tp, None))
    wo = use_weight(p["wo"], ctx, P(ctx.tp, None))
    q = jnp.einsum("bsd,dhk->bshk", h, wq)
    if cfg.qk_norm:
        q = apply_norm(p["q_norm"], q, "rmsnorm")

    is_cross = xkv is not None
    if is_cross:
        if cache is not None and mode == "decode":
            k, v, kpos = cache["k"], cache["v"], cache["pos"]
        else:
            k = jnp.einsum("bsd,dhk->bshk", xkv, wk)
            v = jnp.einsum("bsd,dhk->bshk", xkv, wv)
            if cfg.qk_norm:
                k = apply_norm(p["k_norm"], k, "rmsnorm")
            kpos = jnp.broadcast_to(
                jnp.arange(k.shape[1], dtype=jnp.int32)[None], k.shape[:2]
            )
        if mode == "decode":
            mask = jnp.broadcast_to(
                (kpos >= 0)[:, None, :], (B, S, kpos.shape[1])
            )  # cross: no causal mask
            out = _gqa_scores_softmax_v(q, k, v, mask, scale)
        else:
            out = _chunked_attention(
                q, k, v, positions, kpos, causal=False, window=None,
                scale=scale, chunk=ctx.attn_chunk,
            )
        new_cache = (
            {"k": k, "v": v, "pos": kpos} if mode == "prefill" else cache
        )
        o = jnp.einsum("bshk,hkd->bsd", out, wo.reshape(-1, dh, D))
        if tp is not None:
            o = tp.maybe_psum(o)
        return o.astype(x.dtype), new_cache

    k = jnp.einsum("bsd,dhk->bshk", h, wk)
    v = jnp.einsum("bsd,dhk->bshk", h, wv)
    if cfg.qk_norm:
        k = apply_norm(p["k_norm"], k, "rmsnorm")
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)

    if mode == "train":
        kpos = positions
        if ctx.attn_impl == "pallas" and window != 0:
            out = ops.attention(
                jnp.moveaxis(q, 1, 2), jnp.moveaxis(k, 1, 2),
                jnp.moveaxis(v, 1, 2), causal=causal, window=window,
                scale=scale, impl="pallas",
            )
            out = jnp.moveaxis(out, 1, 2)
        else:
            out = _chunked_attention(
                q, k, v, positions, kpos, causal=causal, window=window,
                scale=scale, chunk=ctx.attn_chunk,
            )
        new_cache = None
    elif mode == "prefill":
        W = cache_len if window is None else min(window, cache_len)
        # ring-buffer write of the last W positions
        kc = jnp.zeros((B, W) + k.shape[2:], k.dtype)
        vc = jnp.zeros_like(kc)
        pc = jnp.full((B, W), -1, jnp.int32)
        take = min(W, S)
        sl = slice(S - take, S)
        idx = positions[:, sl] % W  # (B, take)
        b_idx = jnp.arange(B)[:, None]
        kc = kc.at[b_idx, idx].set(k[:, sl])
        vc = vc.at[b_idx, idx].set(v[:, sl])
        pc = pc.at[b_idx, idx].set(positions[:, sl])
        out = _chunked_attention(
            q, k, v, positions, positions, causal=causal, window=window,
            scale=scale, chunk=ctx.attn_chunk,
        )
        new_cache = {"k": kc, "v": vc, "pos": pc}
    elif mode == "decode" and page_table is not None:
        if window is not None:
            raise ValueError("paged decode does not support local windows")
        kp, vp, pp = cache["k"], cache["v"], cache["pos"]  # page pools
        T = kp.shape[1]  # page_tokens
        pos = positions[:, 0]  # (B,)
        # the write page: COW-resolved and materialised by the host before
        # the step, so live rows never collide (dead rows all target the
        # scratch page with identical values — deterministic scatter)
        phys = page_table[jnp.arange(B), pos // T]
        slot = pos % T
        kp = kp.at[phys, slot].set(k[:, 0])
        vp = vp.at[phys, slot].set(v[:, 0])
        pp = pp.at[phys, slot].set(pos)
        out = ops.paged_attention(
            q[:, 0], kp, vp, page_table, pos + 1, scale=scale,
            impl=kmode.decode_attn_impl(ctx.attn_impl),
        )[:, None]
        new_cache = {"k": kp, "v": vp, "pos": pp}
    elif mode == "decode":
        kc, vc, pc = cache["k"], cache["v"], cache["pos"]
        W = kc.shape[1]
        pos = positions[:, 0]  # (B,)
        slot = pos % W
        b_idx = jnp.arange(B)
        kc = kc.at[b_idx, slot].set(k[:, 0])
        vc = vc.at[b_idx, slot].set(v[:, 0])
        pc = pc.at[b_idx, slot].set(pos)
        mask = pc[:, None, :] >= 0  # (B, 1, W)
        mask &= pc[:, None, :] <= pos[:, None, None]
        if window is not None:
            mask &= (pos[:, None, None] - pc[:, None, :]) < window
        out = _gqa_scores_softmax_v(q, kc, vc, mask, scale)
        new_cache = {"k": kc, "v": vc, "pos": pc}
    else:
        raise ValueError(mode)

    o = jnp.einsum("bshk,hkd->bsd", out, wo.reshape(-1, dh, D))
    if tp is not None:
        o = tp.maybe_psum(o)
    return o.astype(x.dtype), new_cache


# --------------------------------------------------------------------------- #
# MLP
# --------------------------------------------------------------------------- #
def mlp_init(cfg: ArchConfig, ctx: RunCtx, key, d_ff: Optional[int] = None):
    D = cfg.d_model
    F = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    params = {
        "norm": norm_init(D),
        "wi": linear_init(ks[0], D, (F,), cfg.dtype),
        "wo": linear_init(ks[2], F, (D,), cfg.dtype),
    }
    specs = {
        "norm": norm_specs(),
        "wi": P("data", ctx.tp),
        "wo": P(ctx.tp, "data"),
    }
    if cfg.mlp_gated:
        params["wg"] = linear_init(ks[1], D, (F,), cfg.dtype)
        specs["wg"] = P("data", ctx.tp)
    return params, specs


def apply_mlp(p: Params, cfg: ArchConfig, x: jax.Array,
              ctx: RunCtx = None, tp=None) -> jax.Array:
    h = apply_norm(p["norm"], x, cfg.norm)
    act = _act(cfg.act)
    ctx = ctx or RunCtx(mesh=None)
    wi = use_weight(p["wi"], ctx, P(None, ctx.tp))
    wo = use_weight(p["wo"], ctx, P(ctx.tp, None))
    if cfg.mlp_gated:
        wg = use_weight(p["wg"], ctx, P(None, ctx.tp))
        z = act(h @ wg) * (h @ wi)
    else:
        z = act(h @ wi)
    y = z @ wo
    if tp is not None:
        y = tp.maybe_psum(y)
    return y.astype(x.dtype)


# --------------------------------------------------------------------------- #
# MoE
# --------------------------------------------------------------------------- #
def moe_init(cfg: ArchConfig, ctx: RunCtx, key):
    D, F, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 6)
    params = {
        "norm": norm_init(D),
        "router": linear_init(ks[0], D, (E,), jnp.float32),
        "wi": _normal(ks[1], (E, D, F), cfg.dtype, 1.0 / math.sqrt(D)),
        "wg": _normal(ks[2], (E, D, F), cfg.dtype, 1.0 / math.sqrt(D)),
        "wo": _normal(ks[3], (E, F, D), cfg.dtype, 1.0 / math.sqrt(F)),
    }
    specs = {
        "norm": norm_specs(),
        "router": P("data", None),
        "wi": P(ctx.tp, "data", None),
        "wg": P(ctx.tp, "data", None),
        "wo": P(ctx.tp, None, "data"),
    }
    if cfg.n_shared_experts:
        sp, ss = mlp_init(cfg, ctx, ks[4], d_ff=cfg.d_ff * cfg.n_shared_experts)
        params["shared"], specs["shared"] = sp, ss
    if cfg.moe_dense_residual:
        dp_, ds = mlp_init(cfg, ctx, ks[5], d_ff=cfg.resolved_d_ff_dense)
        params["dense_res"], specs["dense_res"] = dp_, ds
    return params, specs


def _moe_local(p, cfg: ArchConfig, ctx: RunCtx, x2d: jax.Array, capacity: int):
    """Single-device reference MoE (router oracle + dense dispatch)."""
    from repro.kernels import ref as kref

    logits = x2d.astype(jnp.float32) @ p["router"]
    e, s, w, keep = ops.moe_router(
        logits, k=cfg.top_k, capacity=capacity, impl="ref"
    )
    buf = kref.moe_dispatch(
        x2d, e, s, keep, n_experts=cfg.n_experts, capacity=capacity
    )
    act = _act(cfg.act)
    hidden = act(jnp.einsum("ecd,edf->ecf", buf, p["wg"])) * jnp.einsum(
        "ecd,edf->ecf", buf, p["wi"]
    )
    out_buf = jnp.einsum("ecf,efd->ecd", hidden, p["wo"])
    return kref.moe_combine(out_buf, e, s, w, keep).astype(x2d.dtype)


def _moe_ep(p, cfg: ArchConfig, ctx: RunCtx, x2d: jax.Array):
    """Expert-parallel MoE: AM-style dispatch + all-to-all over ``model``.

    Tokens are sharded over (data[, model]); experts over model.  Inside the
    shard_map each device routes its local tokens into per-expert capacity
    buffers (Active-Message send queues), the buffers are exchanged with an
    all-to-all through the GAS engine, experts compute at home, and results
    travel back the same way.
    """
    from repro.core.engine import make_engine
    from repro.kernels import ref as kref

    mesh = ctx.mesh
    tp = ctx.tp
    tp_size = ctx.tp_size
    E = cfg.n_experts
    E_l = E // tp_size
    T, D = x2d.shape
    tok_axes = ctx.dp + ((tp,) if T % (ctx.dp_size * tp_size) == 0 else ())
    n_shards = math.prod(mesh.shape[a] for a in tok_axes)
    T_l = T // n_shards
    C_l = max(4, int(math.ceil(T_l * cfg.top_k * cfg.capacity_factor / E)))

    data_axes = tuple(a for a in ctx.dp if a == "data")

    def body(x_l, router_w, wi, wg, wo):
        eng = make_engine(ctx.moe_backend, tp, tp_size)
        if data_axes:
            # FSDP unshard-at-use for expert weights (explicit all-gather
            # over the data axis INSIDE the EP region; its transpose is the
            # reduce-scatter of expert grads).  Without this the shard_map
            # boundary re-gathers the full stacked experts every layer —
            # the dominant all-gather cost measured in the kimi baseline.
            wi = lax.all_gather(wi, data_axes, axis=1, tiled=True)
            wg = lax.all_gather(wg, data_axes, axis=1, tiled=True)
            wo = lax.all_gather(wo, data_axes, axis=1, tiled=True)
        logits = x_l.astype(jnp.float32) @ router_w
        e, s, w, keep = kref.route_topk(
            logits, k=cfg.top_k, capacity=C_l, renormalize=True
        )
        buf = kref.moe_dispatch(x_l, e, s, keep, n_experts=E, capacity=C_l)
        # (E, C_l, D) -> exchange so expert home devices receive all shards
        send = buf.reshape(tp_size * E_l * C_l, D)
        recv = eng.all_to_all(send)
        rows = recv.reshape(tp_size, E_l, C_l, D).transpose(1, 0, 2, 3)
        rows = rows.reshape(E_l, tp_size * C_l, D)
        act = _act(cfg.act)
        hid = act(jnp.einsum("ecd,edf->ecf", rows, wg)) * jnp.einsum(
            "ecd,edf->ecf", rows, wi
        )
        out_rows = jnp.einsum("ecf,efd->ecd", hid, wo)
        back = out_rows.reshape(E_l, tp_size, C_l, D).transpose(1, 0, 2, 3)
        back = eng.all_to_all(back.reshape(tp_size * E_l * C_l, D))
        out_buf = back.reshape(E, C_l, D)
        y = kref.moe_combine(out_buf, e, s, w, keep)
        return y.astype(x_l.dtype)

    tok_spec = P(tok_axes, None)
    expert_spec = P(tp, "data", None)  # matches moe_init specs (FSDP dim 1)
    out = shard_map(
        body,
        mesh=mesh,
        in_specs=(
            tok_spec,
            P(None, None),  # router replicated
            expert_spec,
            expert_spec,
            expert_spec,
        ),
        out_specs=tok_spec,
        check_vma=False,
    )(x2d, p["router"], p["wi"], p["wg"], p["wo"])
    return out


def apply_moe(p: Params, cfg: ArchConfig, ctx: RunCtx, x: jax.Array) -> jax.Array:
    B, S, D = x.shape
    h = apply_norm(p["norm"], x, cfg.norm)
    x2d = h.reshape(B * S, D)
    use_ep = (
        ctx.moe_mode == "ep_shardmap"
        or (
            ctx.moe_mode == "auto"
            and ctx.mesh is not None
            and cfg.n_experts % max(ctx.tp_size, 1) == 0
            and (B * S) % ctx.dp_size == 0
        )
    )
    if use_ep and ctx.mesh is not None:
        y2d = _moe_ep(p, cfg, ctx, x2d)
    else:
        cap = max(4, int(math.ceil(B * S * cfg.top_k * cfg.capacity_factor
                                   / cfg.n_experts)))
        y2d = _moe_local(p, cfg, ctx, x2d, cap)
    y = y2d.reshape(B, S, D)
    if "shared" in p:
        y = y + apply_mlp(p["shared"], cfg, x, ctx)
    if "dense_res" in p:
        y = y + apply_mlp(p["dense_res"], cfg, x, ctx)
    return y.astype(x.dtype)


# --------------------------------------------------------------------------- #
# causal conv (width w, depthwise)
# --------------------------------------------------------------------------- #
def causal_conv(x: jax.Array, w: jax.Array, b: Optional[jax.Array],
                state: Optional[jax.Array] = None):
    """Depthwise causal conv1d.  x: (B, S, C), w: (W, C).

    With ``state`` (B, W-1, C): uses it as left context (decode/chunked);
    returns (y, new_state).
    """
    W = w.shape[0]
    if state is None:
        xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    y = sum(
        xp[:, i : i + x.shape[1]] * w[i][None, None, :] for i in range(W)
    )
    if b is not None:
        y = y + b[None, None, :]
    new_state = xp[:, -(W - 1):] if W > 1 else jnp.zeros_like(x[:, :0])
    return y.astype(x.dtype), new_state


# --------------------------------------------------------------------------- #
# mamba1 mixer
# --------------------------------------------------------------------------- #
def mamba_init(cfg: ArchConfig, ctx: RunCtx, key):
    D = cfg.d_model
    Di = cfg.resolved_d_inner
    N = cfg.ssm_state
    R = cfg.resolved_dt_rank
    Wc = cfg.conv_width
    ks = jax.random.split(key, 6)
    params = {
        "norm": norm_init(D),
        # separate x/gate projections: a fused (D, 2*Di) + split would
        # straddle the tp sharding boundary and cost a collective-permute
        # per layer (measured §Perf falcon iteration C)
        "in_x": linear_init(ks[0], D, (Di,), cfg.dtype),
        "in_gate": linear_init(jax.random.fold_in(ks[0], 1), D, (Di,),
                               cfg.dtype),
        "conv_w": _normal(ks[1], (Wc, Di), cfg.dtype, 1.0 / math.sqrt(Wc)),
        "conv_b": jnp.zeros((Di,), cfg.dtype),
        "x_proj": linear_init(ks[2], Di, (R + 2 * N,), cfg.dtype),
        "dt_proj": linear_init(ks[3], R, (Di,), cfg.dtype),
        "dt_bias": jnp.log(
            jnp.expm1(
                jnp.exp(
                    jax.random.uniform(
                        ks[4], (Di,), jnp.float32,
                        math.log(1e-3), math.log(1e-1),
                    )
                )
            )
        ).astype(jnp.float32),
        "a_log": jnp.log(
            jnp.tile(jnp.arange(1, N + 1, dtype=jnp.float32)[None], (Di, 1))
        ),
        "d_skip": jnp.ones((Di,), jnp.float32),
        "out_proj": linear_init(ks[5], Di, (D,), cfg.dtype),
    }
    specs = {
        "norm": norm_specs(),
        "in_x": P("data", ctx.tp),
        "in_gate": P("data", ctx.tp),
        "conv_w": P(None, ctx.tp),
        "conv_b": P(ctx.tp),
        "x_proj": P(ctx.tp, None),
        "dt_proj": P(None, ctx.tp),
        "dt_bias": P(ctx.tp),
        "a_log": P(ctx.tp, None),
        "d_skip": P(ctx.tp),
        "out_proj": P(ctx.tp, "data"),
    }
    return params, specs


def apply_mamba(
    p: Params,
    cfg: ArchConfig,
    ctx: RunCtx,
    x: jax.Array,
    *,
    mode: str = "train",
    cache: Optional[Params] = None,
) -> Tuple[jax.Array, Optional[Params]]:
    B, S, D = x.shape
    N = cfg.ssm_state
    R = cfg.resolved_dt_rank
    h = apply_norm(p["norm"], x, cfg.norm)
    w_inx = use_weight(p["in_x"], ctx, P(None, ctx.tp))
    w_ing = use_weight(p["in_gate"], ctx, P(None, ctx.tp))
    w_out = use_weight(p["out_proj"], ctx, P(ctx.tp, None))
    xin = h @ w_inx  # (B, S, Di)
    gate = h @ w_ing

    conv_state = cache["conv"] if cache is not None else None
    xin, new_conv = causal_conv(xin, p["conv_w"], p["conv_b"], conv_state)
    xin = jax.nn.silu(xin)

    dbc = xin @ p["x_proj"]  # (B, S, R+2N)
    dt_low, bmat, cmat = jnp.split(dbc, [R, R + N], axis=-1)
    dt = jax.nn.softplus(dt_low @ p["dt_proj"] + p["dt_bias"]).astype(jnp.float32)
    a = -jnp.exp(p["a_log"])  # (Di, N)

    if mode == "decode":
        # single-step closed form
        hprev = cache["ssm"]  # (B, Di, N) f32
        dtt = dt[:, 0]  # (B, Di)
        xt = xin[:, 0].astype(jnp.float32)
        bt = bmat[:, 0].astype(jnp.float32)
        ct = cmat[:, 0].astype(jnp.float32)
        decay = jnp.exp(dtt[..., None] * a[None])
        hnew = decay * hprev + (dtt * xt)[..., None] * bt[:, None, :]
        y = (hnew * ct[:, None, :]).sum(-1) + p["d_skip"][None] * xt
        y = y[:, None, :]
        new_cache = {"conv": new_conv, "ssm": hnew}
    else:
        y = ops.selective_scan(
            xin, dt, a, bmat, cmat, p["d_skip"],
            impl=ctx.scan_impl,
        )
        if mode == "prefill":
            # final SSM state for decode continuation (exact oracle scan;
            # fusing this into the y-scan is a TPU-path optimization).
            hfin = _mamba_final_state(xin, dt, a, bmat)
            new_cache = {"conv": new_conv, "ssm": hfin}
        else:
            new_cache = None

    y = (y * jax.nn.silu(gate.astype(jnp.float32))).astype(x.dtype)
    out = y @ w_out
    return out.astype(x.dtype), new_cache


def _mamba_final_state(xin, dt, a, bmat):
    """Final SSM state h_S (B, Di, N) via lax.scan (f32)."""

    def step(h, inp):
        xt, dtt, bt = inp
        decay = jnp.exp(dtt[..., None] * a[None])
        h = decay * h + (dtt * xt)[..., None] * bt[:, None, :]
        return h, None

    B, S, Di = xin.shape
    N = a.shape[1]
    xs = (
        jnp.moveaxis(xin.astype(jnp.float32), 1, 0),
        jnp.moveaxis(dt.astype(jnp.float32), 1, 0),
        jnp.moveaxis(bmat.astype(jnp.float32), 1, 0),
    )
    h0 = jnp.zeros((B, Di, N), jnp.float32)
    hfin, _ = lax.scan(step, h0, xs)
    return hfin


# --------------------------------------------------------------------------- #
# RG-LRU mixer (griffin / recurrentgemma)
# --------------------------------------------------------------------------- #
def rec_init(cfg: ArchConfig, ctx: RunCtx, key):
    D = cfg.d_model
    W = cfg.resolved_lru_width
    Wc = cfg.conv_width
    ks = jax.random.split(key, 6)
    params = {
        "norm": norm_init(D),
        "in_x": linear_init(ks[0], D, (W,), cfg.dtype),
        "in_gate": linear_init(ks[1], D, (W,), cfg.dtype),
        "conv_w": _normal(ks[2], (Wc, W), cfg.dtype, 1.0 / math.sqrt(Wc)),
        "conv_b": jnp.zeros((W,), cfg.dtype),
        "w_rgate": linear_init(ks[3], W, (W,), cfg.dtype),
        "w_igate": linear_init(ks[4], W, (W,), cfg.dtype),
        "lam": jax.random.uniform(ks[5], (W,), jnp.float32, 0.5, 4.0),
        "out_proj": linear_init(jax.random.fold_in(key, 7), W, (D,), cfg.dtype),
    }
    specs = {
        "norm": norm_specs(),
        "in_x": P("data", ctx.tp),
        "in_gate": P("data", ctx.tp),
        "conv_w": P(None, ctx.tp),
        "conv_b": P(ctx.tp),
        "w_rgate": P("data", ctx.tp),
        "w_igate": P("data", ctx.tp),
        "lam": P(ctx.tp),
        "out_proj": P(ctx.tp, "data"),
    }
    return params, specs


_RGLRU_C = 8.0


def apply_rec(
    p: Params,
    cfg: ArchConfig,
    ctx: RunCtx,
    x: jax.Array,
    *,
    mode: str = "train",
    cache: Optional[Params] = None,
) -> Tuple[jax.Array, Optional[Params]]:
    B, S, D = x.shape
    h = apply_norm(p["norm"], x, cfg.norm)
    w_inx = use_weight(p["in_x"], ctx, P(None, ctx.tp))
    w_ing = use_weight(p["in_gate"], ctx, P(None, ctx.tp))
    w_rg = use_weight(p["w_rgate"], ctx, P(None, ctx.tp))
    w_ig = use_weight(p["w_igate"], ctx, P(None, ctx.tp))
    w_outp = use_weight(p["out_proj"], ctx, P(ctx.tp, None))
    xb = h @ w_inx  # (B, S, W)
    gb = jax.nn.gelu((h @ w_ing).astype(jnp.float32))

    conv_state = cache["conv"] if cache is not None else None
    xb, new_conv = causal_conv(xb, p["conv_w"], p["conv_b"], conv_state)

    xf = xb.astype(jnp.float32)
    r = jax.nn.sigmoid(xf @ w_rg.astype(jnp.float32))
    i = jax.nn.sigmoid(xf @ w_ig.astype(jnp.float32))
    log_a = -_RGLRU_C * jax.nn.softplus(p["lam"])[None, None] * r
    a = jnp.exp(log_a)
    b = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-9)) * (i * xf)

    if mode == "decode":
        hprev = cache["h"]  # (B, W) f32
        hnew = a[:, 0] * hprev + b[:, 0]
        y = hnew[:, None, :]
        new_cache = {"conv": new_conv, "h": hnew}
    else:
        y = ops.gated_linear_scan(a, b, impl=ctx.scan_impl)
        new_cache = (
            {"conv": new_conv, "h": y[:, -1, :].astype(jnp.float32)}
            if mode == "prefill"
            else None
        )

    out = (y * gb).astype(x.dtype) @ w_outp
    return out.astype(x.dtype), new_cache
