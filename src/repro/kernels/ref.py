"""Pure-jnp oracles for every kernel in ``repro.kernels``.

These are the semantic ground truth: simple, obviously-correct, unfused
implementations that the Pallas kernels are swept against (shapes × dtypes)
in ``tests/test_kernels.py``.  They are also the "software node" compute
path in the paper's sense — the version you verify first, then migrate to
the hardware engine.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

__all__ = [
    "ring_shift",
    "perm_put",
    "all_gather",
    "reduce_scatter",
    "all_reduce",
    "all_to_all",
    "attention",
    "paged_attention",
    "route_topk",
    "selective_scan",
    "gated_linear_scan",
]


# --------------------------------------------------------------------------- #
# GAScore collectives: oracles act on the GLOBAL (n_nodes-leading) array
# --------------------------------------------------------------------------- #
def ring_shift(x_global: np.ndarray, k: int) -> np.ndarray:
    """Node (i+k) receives node i's data == roll by +k along axis 0."""
    return np.roll(x_global, k, axis=0)


def perm_put(x_global: np.ndarray, dst: Tuple[int, ...]) -> np.ndarray:
    out = np.zeros_like(x_global)
    for s, d in enumerate(dst):
        out[d] = x_global[s]
    return out


def all_gather(x_global: np.ndarray) -> np.ndarray:
    """(n, m, ...) locals -> every node holds the (n*m, ...) concatenation."""
    n = x_global.shape[0]
    full = x_global.reshape((n * x_global.shape[1],) + x_global.shape[2:])
    return np.stack([full] * n)


def reduce_scatter(x_global: np.ndarray) -> np.ndarray:
    """(n, n*m, ...) contributions -> node i holds sum over nodes of chunk i."""
    n = x_global.shape[0]
    m = x_global.shape[1] // n
    summed = x_global.sum(axis=0).reshape((n, m) + x_global.shape[2:])
    return summed


def all_reduce(x_global: np.ndarray) -> np.ndarray:
    s = x_global.sum(axis=0)
    return np.stack([s] * x_global.shape[0])


def all_to_all(x_global: np.ndarray) -> np.ndarray:
    """(n, n*m, ...) -> out[r, s*m:(s+1)*m] = x[s, r*m:(r+1)*m]."""
    n = x_global.shape[0]
    m = x_global.shape[1] // n
    blocks = x_global.reshape((n, n, m) + x_global.shape[2:])
    return np.swapaxes(blocks, 0, 1).reshape(x_global.shape)


# --------------------------------------------------------------------------- #
# attention
# --------------------------------------------------------------------------- #
def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Unfused softmax attention with GQA/causal/window; f32 internals."""
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    group = Hq // Hkv
    if scale is None:
        scale = 1.0 / (D**0.5)
    kx = jnp.repeat(k, group, axis=1)
    vx = jnp.repeat(v, group, axis=1)
    s = jnp.einsum(
        "bhqd,bhkd->bhqk",
        q.astype(jnp.float32) * scale,
        kx.astype(jnp.float32),
    )
    qpos = jnp.arange(Sq)[:, None]
    kpos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= (qpos - kpos) < window
        if not causal:
            mask &= (kpos - qpos) < window
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    # fully-masked rows give uniform p; zero them like the kernel does
    any_visible = mask.any(axis=-1)[None, None, :, None]
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vx.astype(jnp.float32))
    out = jnp.where(any_visible, out, 0.0)
    return out.astype(q.dtype)


def paged_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_table: jax.Array,
    lengths: jax.Array,
    layer: Optional[jax.Array] = None,
    *,
    scale: Optional[float] = None,
) -> jax.Array:
    """Decode attention reading K/V through a page table (unfused oracle).

    q: (B, Hq, D) — one query token per request.
    k_pages / v_pages: (P, T, Hkv, D) — the physical page pool: P pages of
      T tokens each (the KV pool's carrier blocks, unflattened); or the
      pools of every layer, (L, P, T, Hkv, D), read at ``layer``.
    page_table: (B, NP) int32 — request b's logical page p lives in
      physical page ``page_table[b, p]``; entries past the live length may
      point anywhere (they are masked).
    lengths: (B,) int32 — number of live cache positions per request.
    layer: int32 scalar, given with a 5-D pool and only then.
    """
    if (k_pages.ndim == 5) != (layer is not None):
        raise ValueError(
            f"a 5-D pool takes a layer, a 4-D one none: {k_pages.shape}, "
            f"layer {layer!r}"
        )
    B, Hq, D = q.shape
    T, Hkv = k_pages.shape[-3:-1]
    NP = page_table.shape[1]
    S = NP * T
    group = Hq // Hkv
    if scale is None:
        scale = 1.0 / (D**0.5)
    if layer is None:
        kg, vg = k_pages[page_table], v_pages[page_table]
    else:
        kg, vg = k_pages[layer, page_table], v_pages[layer, page_table]
    # gather: (B, NP, T, Hkv, D) -> (B, Hkv, S, D)
    kd = jnp.moveaxis(kg.reshape(B, S, Hkv, D), 1, 2)
    vd = jnp.moveaxis(vg.reshape(B, S, Hkv, D), 1, 2)
    kx = jnp.repeat(kd, group, axis=1)  # (B, Hq, S, D)
    vx = jnp.repeat(vd, group, axis=1)
    s = jnp.einsum(
        "bhd,bhsd->bhs",
        q.astype(jnp.float32) * scale,
        kx.astype(jnp.float32),
    )
    valid = jnp.arange(S)[None, :] < lengths[:, None]  # (B, S)
    s = jnp.where(valid[:, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    # zero V at masked positions too: masked probabilities are ~0 but
    # 0 * NaN = NaN, and padded table slots may point at garbage pages
    vx = jnp.where(valid[:, None, :, None], vx.astype(jnp.float32), 0.0)
    out = jnp.einsum("bhs,bhsd->bhd", p, vx)
    any_visible = valid.any(axis=-1)[:, None, None]
    return jnp.where(any_visible, out, 0.0).astype(q.dtype)


# --------------------------------------------------------------------------- #
# MoE routing
# --------------------------------------------------------------------------- #
def route_topk(
    logits: jax.Array, *, k: int, capacity: int, renormalize: bool = True
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Sequential-oracle top-k routing with capacity slots (token order)."""
    T, E = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    w, e = jax.lax.top_k(probs, k)  # (T, K)
    if renormalize:
        w = w / jnp.maximum(w.sum(axis=-1, keepdims=True), 1e-9)
    flat_e = e.reshape(-1)
    oh = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    excl = jnp.cumsum(oh, axis=0) - oh
    slot = (excl * oh).sum(-1)
    keep = slot < capacity
    return (
        e.astype(jnp.int32),
        slot.reshape(T, k).astype(jnp.int32),
        w.astype(jnp.float32),
        keep.reshape(T, k),
    )


def moe_dispatch(
    tokens: jax.Array,
    expert_idx: jax.Array,
    slot: jax.Array,
    keep: jax.Array,
    *,
    n_experts: int,
    capacity: int,
) -> jax.Array:
    """(T, D) tokens -> (E, C, D) expert buffers (dropped rows zero)."""
    T, D = tokens.shape
    K = expert_idx.shape[1]
    buf = jnp.zeros((n_experts, capacity, D), tokens.dtype)
    for j in range(K):
        e = expert_idx[:, j]
        s = jnp.where(keep[:, j], slot[:, j], 0)
        contrib = jnp.where(keep[:, j, None], tokens, 0)
        buf = buf.at[e, s].add(contrib, mode="drop")
    return buf


def moe_combine(
    expert_out: jax.Array,
    expert_idx: jax.Array,
    slot: jax.Array,
    weight: jax.Array,
    keep: jax.Array,
) -> jax.Array:
    """(E, C, D) expert outputs -> (T, D) weighted combination."""
    T, K = expert_idx.shape
    rows = expert_out[expert_idx, slot]  # (T, K, D)
    w = jnp.where(keep, weight, 0.0)
    return (rows * w[..., None]).sum(axis=1).astype(expert_out.dtype)


# --------------------------------------------------------------------------- #
# scans
# --------------------------------------------------------------------------- #
def selective_scan(
    x: jax.Array,
    dt: jax.Array,
    a: jax.Array,
    b: jax.Array,
    c: jax.Array,
    d: jax.Array,
) -> jax.Array:
    """lax.scan oracle of the mamba1 recurrence (f32 internals)."""
    B, S, Di = x.shape
    N = a.shape[1]
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    af = a.astype(jnp.float32)
    bf = b.astype(jnp.float32)
    cf = c.astype(jnp.float32)
    df = d.astype(jnp.float32)

    def step(h, inp):
        xt, dtt, bt, ct = inp  # (B,Di) (B,Di) (B,N) (B,N)
        decay = jnp.exp(dtt[..., None] * af[None])  # (B, Di, N)
        drive = (dtt * xt)[..., None] * bt[:, None, :]
        h = decay * h + drive
        yt = (h * ct[:, None, :]).sum(-1) + df[None] * xt
        return h, yt

    h0 = jnp.zeros((B, Di, N), jnp.float32)
    xs = (
        jnp.moveaxis(xf, 1, 0),
        jnp.moveaxis(dtf, 1, 0),
        jnp.moveaxis(bf, 1, 0),
        jnp.moveaxis(cf, 1, 0),
    )
    _, ys = jax.lax.scan(step, h0, xs)
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype)


def selective_scan_chunked(
    x: jax.Array,
    dt: jax.Array,
    a: jax.Array,
    b: jax.Array,
    c: jax.Array,
    d: jax.Array,
    chunk: int = 128,
) -> jax.Array:
    """Chunked associative-scan mamba1 (exact; no per-timestep ops).

    The per-timestep ``lax.scan`` form emits one tiny collective per step in
    the backward pass when d_inner is tensor-sharded (measured: ~5e5
    all-reduces for falcon train_4k).  This form runs
    ``lax.associative_scan`` inside fixed-size chunks — decay factors stay
    in (0, 1] so the product form is numerically safe — and carries the
    state across chunks, reducing the sequential depth from S to S/chunk
    and the backward collectives by the same factor.
    """
    B, S, Di = x.shape
    N = a.shape[1]
    chunk = min(chunk, S)
    pad = (-S) % chunk
    xf = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, pad), (0, 0)))
    dtf = jnp.pad(dt.astype(jnp.float32), ((0, 0), (0, pad), (0, 0)))
    bf = jnp.pad(b.astype(jnp.float32), ((0, 0), (0, pad), (0, 0)))
    cf = jnp.pad(c.astype(jnp.float32), ((0, 0), (0, pad), (0, 0)))
    af = a.astype(jnp.float32)
    df = d.astype(jnp.float32)
    nc = xf.shape[1] // chunk

    def combine(l, r):
        al, bl = l
        ar, br = r
        return al * ar, br + ar * bl

    def chunk_step(h0, inp):
        xt, dtt, bt, ct = inp  # (B,c,Di) (B,c,Di) (B,c,N) (B,c,N)
        decay = jnp.exp(dtt[..., None] * af[None, None])  # (B,c,Di,N)
        drive = (dtt * xt)[..., None] * bt[:, :, None, :]
        A, Bv = lax.associative_scan(combine, (decay, drive), axis=1)
        h = A * h0[:, None] + Bv  # (B,c,Di,N)
        y = (h * ct[:, :, None, :]).sum(-1) + df[None, None] * xt
        return h[:, -1], y

    xs = tuple(
        jnp.moveaxis(t.reshape(B, nc, chunk, -1), 1, 0)
        for t in (xf, dtf, bf, cf)
    )
    h0 = jnp.zeros((B, Di, N), jnp.float32)
    _, ys = lax.scan(chunk_step, h0, xs)
    y = jnp.moveaxis(ys, 0, 1).reshape(B, nc * chunk, Di)[:, :S]
    return y.astype(x.dtype)


def gated_linear_scan_chunked(a: jax.Array, b: jax.Array,
                              chunk: int = 256) -> jax.Array:
    """Chunked associative form of h_t = a_t h_{t-1} + b_t (see above)."""
    B, S, D = a.shape
    chunk = min(chunk, S)
    pad = (-S) % chunk
    af = jnp.pad(a.astype(jnp.float32), ((0, 0), (0, pad), (0, 0)),
                 constant_values=1.0)
    bf = jnp.pad(b.astype(jnp.float32), ((0, 0), (0, pad), (0, 0)))
    nc = af.shape[1] // chunk

    def combine(l, r):
        al, bl = l
        ar, br = r
        return al * ar, br + ar * bl

    def chunk_step(h0, inp):
        at, bt = inp
        A, Bv = lax.associative_scan(combine, (at, bt), axis=1)
        h = A * h0[:, None] + Bv
        return h[:, -1], h

    xs = tuple(
        jnp.moveaxis(t.reshape(B, nc, chunk, D), 1, 0) for t in (af, bf)
    )
    h0 = jnp.zeros((B, D), jnp.float32)
    _, ys = lax.scan(chunk_step, h0, xs)
    y = jnp.moveaxis(ys, 0, 1).reshape(B, nc * chunk, D)[:, :S]
    return y.astype(b.dtype)


def gated_linear_scan(a: jax.Array, b: jax.Array) -> jax.Array:
    """lax.scan oracle of h_t = a_t h_{t-1} + b_t (f32 internals)."""

    def step(h, inp):
        at, bt = inp
        h = at * h + bt
        return h, h

    af = jnp.moveaxis(a.astype(jnp.float32), 1, 0)
    bf = jnp.moveaxis(b.astype(jnp.float32), 1, 0)
    h0 = jnp.zeros(af.shape[1:], jnp.float32)
    _, ys = jax.lax.scan(step, h0, (af, bf))
    return jnp.moveaxis(ys, 0, 1).astype(b.dtype)
