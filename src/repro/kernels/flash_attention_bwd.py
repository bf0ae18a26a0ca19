"""Blockwise (flash) attention BACKWARD kernels + custom-VJP wrapper.

Standard two-kernel formulation (Dao et al., adapted to TPU tiling):

  forward (``flash_attention.py`` with ``return_lse=True``) additionally
  emits the per-row log-sum-exp L = m + log(l), so the backward pass can
  recompute the probability tiles p = exp(q·kᵀ·scale − L) exactly without
  storing the (S × S) matrix.

  delta   = rowsum(dO ⊙ O)                       (jnp; one fused pass)
  dKV     : grid (B, KH, kv-blocks); inner loop over the GQA group's query
            heads × q-blocks, accumulating
              dV += pᵀ · dO
              dK += (p ⊙ (dO·Vᵀ − delta))ᵀ · q · scale
  dQ      : grid (B, Hq, q-blocks); inner loop over kv-blocks accumulating
              dQ += (p ⊙ (dO·Vᵀ − delta)) · K · scale

Both kernels stage tiles through VMEM via BlockSpecs with f32 accumulators
in scratch; the MXU sees (block × head_dim)·(head_dim × block) shapes.
The public entry point is ``flash_attention_vjp`` — a ``jax.custom_vjp``
drop-in whose gradients are swept against ``jax.grad`` of the pure-jnp
oracle in tests/test_kernels.py.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import mode

from repro.kernels.flash_attention import flash_attention

__all__ = ["flash_attention_vjp"]

NEG_INF = -1e30


def _mask(qpos, kpos, causal, window):
    m = jnp.ones(qpos.shape, bool)
    if causal:
        m &= qpos >= kpos
    if window is not None:
        m &= (qpos - kpos) < window
        if not causal:
            m &= (kpos - qpos) < window
    return m


# --------------------------------------------------------------------------- #
# dK/dV kernel: one (kv-block, kv-head) per grid step, loop q side inside
# --------------------------------------------------------------------------- #
def _dkv_kernel(
    q_ref,      # (1, 1, G, Sq, D)   all q rows of this kv head's group
    k_ref,      # (1, 1, BK, D)
    v_ref,      # (1, 1, BK, D)
    do_ref,     # (1, 1, G, Sq, D)
    lse_ref,    # (1, 1, G, Sq)
    delta_ref,  # (1, 1, G, Sq)
    dk_ref,     # (1, 1, BK, D)
    dv_ref,     # (1, 1, BK, D)
    dk_scr,
    dv_scr,
    *,
    scale: float,
    causal: bool,
    window: Optional[int],
    block_q: int,
    block_k: int,
    group: int,
):
    ki = pl.program_id(2)
    seq_q = q_ref.shape[3]
    nq = seq_q // block_q
    dk_scr[...] = jnp.zeros_like(dk_scr)
    dv_scr[...] = jnp.zeros_like(dv_scr)
    k = k_ref[0, 0].astype(jnp.float32)  # (BK, D)
    v = v_ref[0, 0].astype(jnp.float32)

    def q_block(idx, _):
        g = idx // nq
        qi = idx % nq
        qs = pl.ds(qi * block_q, block_q)
        q = q_ref[0, 0, g, qs, :].astype(jnp.float32)
        do = do_ref[0, 0, g, qs, :].astype(jnp.float32)
        lse = lse_ref[0, 0, g, qs]
        delta = delta_ref[0, 0, g, qs]
        s = lax.dot_general(
            q * scale, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (BQ, BK)
        qpos = qi * block_q + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kpos = ki * block_k + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        msk = _mask(qpos, kpos, causal, window)
        p = jnp.where(msk, jnp.exp(s - lse[:, None]), 0.0)  # (BQ, BK)
        # dV += p^T @ dO
        dv_scr[...] += lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # dS = p * (dO @ V^T - delta)
        dov = lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dov - delta[:, None])
        # dK += dS^T @ q * scale
        dk_scr[...] += lax.dot_general(
            ds, q * scale, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return 0

    lax.fori_loop(0, group * nq, q_block, 0)
    dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
    dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


# --------------------------------------------------------------------------- #
# dQ kernel: one (q-block, q-head) per grid step, loop kv side inside
# --------------------------------------------------------------------------- #
def _dq_kernel(
    q_ref,      # (1, 1, BQ, D)
    k_ref,      # (1, 1, Sk, D)
    v_ref,      # (1, 1, Sk, D)
    do_ref,     # (1, 1, BQ, D)
    lse_ref,    # (1, 1, BQ)
    delta_ref,  # (1, 1, BQ)
    dq_ref,     # (1, 1, BQ, D)
    dq_scr,
    *,
    scale: float,
    causal: bool,
    window: Optional[int],
    block_q: int,
    block_k: int,
):
    qi = pl.program_id(2)
    seq_k = k_ref.shape[2]
    nk = seq_k // block_k
    dq_scr[...] = jnp.zeros_like(dq_scr)
    q = q_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0]
    delta = delta_ref[0, 0]

    def kv_block(ki, _):
        ks_ = pl.ds(ki * block_k, block_k)
        k = k_ref[0, 0, ks_, :].astype(jnp.float32)
        v = v_ref[0, 0, ks_, :].astype(jnp.float32)
        s = lax.dot_general(
            q * scale, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        qpos = qi * block_q + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kpos = ki * block_k + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        msk = _mask(qpos, kpos, causal, window)
        p = jnp.where(msk, jnp.exp(s - lse[:, None]), 0.0)
        dov = lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dov - delta[:, None])
        dq_scr[...] += lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        return 0

    lax.fori_loop(0, nk, kv_block, 0)
    dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


# --------------------------------------------------------------------------- #
# custom VJP wrapper
# --------------------------------------------------------------------------- #
@functools.partial(
    jax.custom_vjp,
    nondiff_argnums=(3, 4, 5, 6, 7),
)
def flash_attention_vjp(
    q, k, v,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
):
    out, _ = _fwd(q, k, v, causal, window, scale, block_q, block_k)
    return out


def _fwd(q, k, v, causal, window, scale, block_q, block_k):
    out, lse = flash_attention(
        q, k, v, causal=causal, window=window, scale=scale,
        block_q=block_q, block_k=block_k,
        return_lse=True,
    )
    return out, (q, k, v, out, lse)


def _bwd(causal, window, scale, block_q, block_k, res, dout):
    q, k, v, out, lse = res
    B, Hq, Sq, D = q.shape
    _, KH, Sk, _ = k.shape
    G = Hq // KH
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    nq, nk = Sq // bq, Sk // bk

    delta = jnp.sum(
        dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )  # (B, Hq, Sq)

    # ---- dK/dV ---------------------------------------------------------- #
    qg = q.reshape(B, KH, G, Sq, D)
    dog = dout.reshape(B, KH, G, Sq, D)
    lseg = lse.reshape(B, KH, G, Sq)
    deltag = delta.reshape(B, KH, G, Sq)

    dkv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, scale=scale, causal=causal, window=window,
            block_q=bq, block_k=bk, group=G,
        ),
        grid=(B, KH, nk),
        in_specs=[
            pl.BlockSpec((1, 1, G, Sq, D), lambda b, h, ki: (b, h, 0, 0, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, ki: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, ki: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, G, Sq, D), lambda b, h, ki: (b, h, 0, 0, 0)),
            pl.BlockSpec((1, 1, G, Sq), lambda b, h, ki: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, G, Sq), lambda b, h, ki: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, D), lambda b, h, ki: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, ki: (b, h, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, KH, Sk, D), k.dtype),
            jax.ShapeDtypeStruct((B, KH, Sk, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=mode.interpret(),
        name="flash_attention_dkv",
    )(qg, k, v, dog, lseg, deltag)
    dk, dv = dkv

    # ---- dQ -------------------------------------------------------------- #
    kx = jnp.repeat(k, G, axis=1) if G > 1 else k
    vx = jnp.repeat(v, G, axis=1) if G > 1 else v
    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, scale=scale, causal=causal, window=window,
            block_q=bq, block_k=bk,
        ),
        grid=(B, Hq, nq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, qi: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, Sk, D), lambda b, h, qi: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, Sk, D), lambda b, h, qi: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bq, D), lambda b, h, qi: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, h, qi: (b, h, qi)),
            pl.BlockSpec((1, 1, bq), lambda b, h, qi: (b, h, qi)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, qi: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=mode.interpret(),
        name="flash_attention_dq",
    )(q, kx, vx, dout, lse, delta)

    return dq, dk, dv


flash_attention_vjp.defvjp(_fwd, _bwd)
