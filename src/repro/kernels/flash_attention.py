"""Blockwise (flash) attention forward kernel for TPU.

The training/prefill compute hot spot.  Standard online-softmax blockwise
algorithm, adapted to the TPU memory hierarchy: Q/K/V tiles are staged
HBM->VMEM by the ``BlockSpec`` pipeline, the running (m, l, acc) state lives
in VMEM scratch and persists across the (sequential, innermost) KV-block
grid dimension, and the two matmuls per tile hit the MXU with
(block_q × head_dim) · (head_dim × block_k) shapes — keep ``block_q``,
``block_k`` multiples of 128 and ``head_dim`` ∈ {64, 128, 256}.

Supports causal masking, GQA (q heads grouped over fewer KV heads, resolved
in the K/V index_map so KV tiles are fetched once per group), and a sliding
local-attention window (gemma3 / recurrentgemma local layers).

Oracle: ``repro.kernels.ref.attention``.  Compiled on a TPU, interpreted
on the CPU (``repro.kernels.mode``).
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

__all__ = ["flash_attention"]

NEG_INF = -1e30


def _fa_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    lse_ref,
    m_scr,
    l_scr,
    acc_scr,
    *,
    scale: float,
    causal: bool,
    window: Optional[int],
    block_q: int,
    block_k: int,
    kv_blocks: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale  # (BQ, D)
    k = k_ref[0, 0].astype(jnp.float32)  # (BK, D)
    v = v_ref[0, 0].astype(jnp.float32)  # (BK, D)

    s = lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (BQ, BK)

    qpos = qi * block_q + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    kpos = ki * block_k + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones_like(s, dtype=bool)
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= (qpos - kpos) < window
        if not causal:
            mask &= (kpos - qpos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[:, 0]
    l_prev = l_scr[:, 0]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    # rows with no visible keys keep m == NEG_INF; exp() there must be 0.
    p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + p.sum(axis=-1)
    acc = acc_scr[...] * alpha[:, None] + lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    m_scr[:, 0] = m_new
    l_scr[:, 0] = l_new
    acc_scr[...] = acc

    @pl.when(ki == kv_blocks - 1)
    def _finalize():
        l = l_scr[:, 0]
        denom = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)
        # log-sum-exp for the backward pass: L = m + log(l)
        lse_ref[0, 0] = m_scr[:, 0] + jnp.log(denom)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal",
        "window",
        "scale",
        "block_q",
        "block_k",
        "return_lse",
    ),
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    return_lse: bool = False,
) -> jax.Array:
    """Blockwise attention.

    Args:
      q: (B, Hq, Sq, D); k, v: (B, Hkv, Sk, D) with Hq % Hkv == 0 (GQA).
      causal: causal mask (positions aligned to sequence end when Sq == Sk).
      window: sliding local-attention window size (None = global).
      scale: softmax scale, default 1/sqrt(D).
      block_q/block_k: VMEM tile sizes (multiples of 128 on target).
    Returns:
      (B, Hq, Sq, D) in q.dtype; with ``return_lse`` also the per-row
      log-sum-exp (B, Hq, Sq) f32 (consumed by the backward kernels).
    """
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    if Hq % Hkv != 0:
        raise ValueError(f"Hq={Hq} not a multiple of Hkv={Hkv}")
    group = Hq // Hkv
    if scale is None:
        scale = 1.0 / (D**0.5)
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    if Sq % block_q or Sk % block_k:
        raise ValueError(
            f"seq ({Sq},{Sk}) not divisible by blocks ({block_q},{block_k})"
        )
    nq, nk = Sq // block_q, Sk // block_k

    kernel = functools.partial(
        _fa_kernel,
        scale=scale,
        causal=causal,
        window=window,
        block_q=block_q,
        block_k=block_k,
        kv_blocks=nk,
    )

    out, lse = pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec(
                (1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, D), lambda b, h, qi, ki: (b, h // group, ki, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, D), lambda b, h, qi, ki: (b, h // group, ki, 0)
            ),
        ],
        out_specs=[
            pl.BlockSpec(
                (1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)
            ),
            pl.BlockSpec((1, 1, block_q), lambda b, h, qi, ki: (b, h, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((B, Hq, Sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=mode.interpret(),
        name="flash_attention_fwd",
    )(q, k, v)
    if return_lse:
        return out, lse
    return out
