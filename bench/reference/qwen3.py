"""Plain float32 reference of a Qwen3 dense decoder (qk-norm GQA,
RoPE, SwiGLU, RMSNorm, tied embeddings), after the published Hugging
Face ``Qwen3ForCausalLM``: no kernels, no cache, no batching tricks.

It takes its sizes from the configuration file and makes its weights
itself from the seed (``bench.weights``), one layer at a time, so it
holds one layer's float32 weights at a time.  Every matmul runs at
"highest" precision.  Departures from the published model: random
weights on an exact grid (``bench.weights``); nothing else.

``lowp="float8_e4m3fn"`` casts both inputs of every matmul to fp8 and
back: the control, one precision step below the configuration's bf16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

F32 = jnp.float32


def sizes(cfg: dict) -> dict:
    return {
        "L": int(cfg["num_hidden_layers"]),
        "D": int(cfg["hidden_size"]),
        "H": int(cfg["num_attention_heads"]),
        "KH": int(cfg["num_key_value_heads"]),
        "dh": int(cfg["head_dim"]),
        "F": int(cfg["intermediate_size"]),
        "V": int(cfg["vocab_size"]),
        "eps": float(cfg["rms_norm_eps"]),
        "theta": float(cfg["rope_theta"]),
    }


def layer_shapes(s: dict) -> dict:
    D, H, KH, dh, F = s["D"], s["H"], s["KH"], s["dh"], s["F"]
    return {
        "attn.norm.scale": (D,),
        "attn.wq": (D, H, dh),
        "attn.wk": (D, KH, dh),
        "attn.wv": (D, KH, dh),
        "attn.wo": (H * dh, D),
        "attn.q_norm.scale": (dh,),
        "attn.k_norm.scale": (dh,),
        "mlp.norm.scale": (D,),
        "mlp.wg": (D, F),
        "mlp.wi": (D, F),
        "mlp.wo": (F, D),
    }


def _q(x, lowp):
    return x if lowp is None else x.astype(lowp).astype(F32)


def _mm(spec, a, b, lowp):
    return jnp.einsum(spec, _q(a, lowp), _q(b, lowp),
                      precision=jax.lax.Precision.HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=F32) / half)
    ang = pos[..., None].astype(F32) * inv  # (N, S, half)
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("s", "lowp"))
def _layer(x, key, l, *, s, lowp):
    s = dict(s)
    w = {n: weights.make(key, n, shp, F32, layer=l)
         for n, shp in layer_shapes(s).items()}
    N, S, _ = x.shape
    H, KH, dh, eps = s["H"], s["KH"], s["dh"], s["eps"]
    pos = jnp.broadcast_to(jnp.arange(S)[None], (N, S))
    h = _rms(x, w["attn.norm.scale"], eps)
    q = _rms(_mm("nsd,dhk->nshk", h, w["attn.wq"], lowp),
             w["attn.q_norm.scale"], eps)
    k = _rms(_mm("nsd,dhk->nshk", h, w["attn.wk"], lowp),
             w["attn.k_norm.scale"], eps)
    v = _mm("nsd,dhk->nshk", h, w["attn.wv"], lowp)
    q, k = _rope(q, pos, s["theta"]), _rope(k, pos, s["theta"])
    q = q.reshape(N, S, KH, H // KH, dh)
    sc = _mm("nqkgd,nskd->nkgqs", q, k, lowp) / jnp.sqrt(F32(dh))
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    sc = jnp.where(causal, sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = _mm("nkgqs,nskd->nqkgd", p, v, lowp).reshape(N, S, H * dh)
    x = x + _mm("nsf,fd->nsd", o, w["attn.wo"], lowp)
    h = _rms(x, w["mlp.norm.scale"], eps)
    z = jax.nn.silu(_mm("nsd,df->nsf", h, w["mlp.wg"], lowp)) * _mm(
        "nsd,df->nsf", h, w["mlp.wi"], lowp)
    return x + _mm("nsf,fd->nsd", z, w["mlp.wo"], lowp)


@functools.partial(jax.jit, static_argnames=("s",))
def _embed(tokens, key, *, s):
    s = dict(s)
    tok = weights.make(key, "tok", (s["V"], s["D"]), F32)
    return tok[tokens]


def hidden(cfg: dict, seed: int, tokens: np.ndarray, sel: np.ndarray,
           lowp=None) -> jax.Array:
    """Final-normed hidden states ``(len(sel), D)`` of the sequences
    ``tokens`` (N, S) at the flat positions ``sel`` (into N * S)."""
    s = sizes(cfg)
    hs = tuple(sorted(s.items()))
    key = weights.key_from_seed(seed)
    with jax.default_matmul_precision("highest"):
        x = _embed(jnp.asarray(tokens, jnp.int32), key, s=hs)
        for l in range(s["L"]):
            x = _layer(x, key, l, s=hs, lowp=lowp)
        x = x.reshape(-1, s["D"])[jnp.asarray(sel)]
        norm_f = weights.make(key, "norm_f.scale", (s["D"],), F32)
        return _rms(x, norm_f, s["eps"])


@functools.partial(jax.jit, static_argnames=("s", "lowp"))
def _gap_block(h_ref, h_low, served, key, *, s, lowp):
    """For each row: the reference's best logit minus the reference's
    logit of the served token, and of the token the low-precision pass
    puts first; both over the row's largest |logit|."""
    s = dict(s)
    tok = weights.make(key, "tok", (s["V"], s["D"]), F32)
    ref = jnp.einsum("md,vd->mv", h_ref, tok,
                     precision=jax.lax.Precision.HIGHEST)
    best = ref.max(-1)
    scale = jnp.abs(ref).max(-1)
    got = jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
    out = [(best - got) / scale]
    if lowp is not None:
        low = _mm("md,vd->mv", h_low, tok, lowp)
        pick = jnp.argmax(low, -1)
        out.append((best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0])
                   / scale)
    return jnp.stack(out)


BLOCK = 128


def gaps(cfg: dict, seed: int, seqs, cache_len: int, n_seqs: int,
         lowp=None) -> np.ndarray:
    """Per served token, the relative gap of its reference logit below
    the reference's best (row 0), and, with ``lowp``, the same for the
    token the low-precision reference puts first (row 1).

    ``seqs`` is a list of ``(prompt, served)`` token lists; they are
    padded to ``n_seqs`` rows of ``cache_len`` positions, so one
    compiled program serves every run."""
    if len(seqs) > n_seqs:
        raise ValueError(f"{len(seqs)} sequences > {n_seqs}")
    tokens = np.zeros((n_seqs, cache_len), np.int32)
    sel, served = [], []
    for n, (prompt, out) in enumerate(seqs):
        seq = list(prompt) + list(out[:-1])
        if len(seq) > cache_len:
            raise ValueError(f"sequence of {len(seq)} > {cache_len}")
        tokens[n, : len(seq)] = seq
        # the logits at position p predict the token at p + 1
        first = len(prompt) - 1
        sel.extend(n * cache_len + first + j for j in range(len(out)))
        served.extend(out)
    m = len(sel)
    pad = (-m) % BLOCK
    sel = np.asarray(sel + [0] * pad, np.int32)
    served = np.asarray(served + [0] * pad, np.int32)
    h_ref = hidden(cfg, seed, tokens, sel)
    h_low = hidden(cfg, seed, tokens, sel, lowp) if lowp else h_ref
    hs = tuple(sorted(sizes(cfg).items()))
    key = weights.key_from_seed(seed)
    rows = []
    with jax.default_matmul_precision("highest"):
        for lo in range(0, len(sel), BLOCK):
            rows.append(np.asarray(_gap_block(
                h_ref[lo: lo + BLOCK], h_low[lo: lo + BLOCK],
                jnp.asarray(served[lo: lo + BLOCK]), key, s=hs, lowp=lowp)))
    return np.concatenate(rows, axis=1)[:, :m]
