"""MoE routing engine: top-k gating + capacity slot assignment, fused.

This kernel is the Active-Message *schedule builder* of the paper realized
for MoE: every routed token is an AM (payload = hidden vector, destination =
expert, handler = expert FFN), and the router computes, per token, the
destination and the *capacity slot* (rank within the destination's buffer) —
exactly the computation ``repro.core.am.build_send_buffer`` performs for
general messages, here fused with softmax gating and iterative top-k on the
VPU so the (T, E) logits are read from HBM once.

The slot counters persist in VMEM scratch across the (sequential) token-
block grid dimension, making the rank assignment globally consistent in
token order — the property the combine step relies on and the hypothesis
tests check (slot uniqueness per expert, conservation of kept tokens).

Dispatch/combine themselves are dense one-hot einsums (``ops.moe_dispatch``/
``ops.moe_combine``) — the GSPMD-friendly form whose all-to-all over the
expert axis is scheduled by the partitioner; the router's slot map is what
makes them capacity-bounded.

Oracle: ``repro.kernels.ref.route_topk``.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import mode

__all__ = ["moe_router"]

NEG_INF = -1e30


def _router_kernel(
    logits_ref,
    eidx_ref,
    slot_ref,
    w_ref,
    keep_ref,
    counts_scr,
    *,
    k: int,
    n_experts: int,
    capacity: int,
    renormalize: bool,
):
    ti = pl.program_id(0)

    @pl.when(ti == 0)
    def _init():
        counts_scr[...] = jnp.zeros_like(counts_scr)

    logits = logits_ref[...].astype(jnp.float32)  # (BT, E)
    bt = logits.shape[0]
    probs = jax.nn.softmax(logits, axis=-1)

    # iterative top-k (k is small: 1..8) — max+mask per step on the VPU
    masked = probs
    eye = lax.broadcasted_iota(jnp.int32, (bt, n_experts), 1)
    top_w = []
    top_e = []
    for _ in range(k):
        w = masked.max(axis=-1)
        e = jnp.argmax(masked, axis=-1).astype(jnp.int32)
        top_w.append(w)
        top_e.append(e)
        masked = jnp.where(eye == e[:, None], NEG_INF, masked)
    wmat = jnp.stack(top_w, axis=1)  # (BT, K)
    emat = jnp.stack(top_e, axis=1)  # (BT, K)
    if renormalize:
        wmat = wmat / jnp.maximum(wmat.sum(axis=1, keepdims=True), 1e-9)

    # capacity slots: rank of each (token, choice) within its expert, in
    # flat token-major order, offset by the running counters.
    flat_e = emat.reshape(-1)  # (BT*K,)
    oh = (flat_e[:, None] == lax.broadcasted_iota(
        jnp.int32, (bt * k, n_experts), 1)).astype(jnp.int32)
    excl = jnp.cumsum(oh, axis=0) - oh  # exclusive in-block rank
    rank_in_block = (excl * oh).sum(axis=-1)
    base = (counts_scr[0][None, :] * oh).sum(axis=-1)  # gather via one-hot dot
    slot = base + rank_in_block
    keep = slot < capacity

    counts_scr[0, :] = counts_scr[0, :] + oh.sum(axis=0)

    eidx_ref[...] = emat
    slot_ref[...] = slot.reshape(bt, k)
    w_ref[...] = wmat.astype(w_ref.dtype)
    keep_ref[...] = keep.reshape(bt, k)


@functools.partial(
    jax.jit,
    static_argnames=("k", "capacity", "renormalize", "block_t"),
)
def moe_router(
    logits: jax.Array,
    *,
    k: int,
    capacity: int,
    renormalize: bool = True,
    block_t: int = 256,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Route tokens to experts with capacity-bounded slot assignment.

    Args:
      logits: (T, E) router logits.
      k: experts per token.
      capacity: per-expert buffer size C; choices ranked >= C are dropped.
      renormalize: renormalize the kept top-k weights to sum to 1.
      block_t: token block per grid step (sequential dimension).
    Returns:
      expert_idx (T, K) int32, slot (T, K) int32, weight (T, K) f32,
      keep (T, K) bool.
    """
    T, E = logits.shape
    block_t = min(block_t, T)
    if T % block_t:
        raise ValueError(f"T={T} not divisible by block_t={block_t}")
    nt = T // block_t

    kernel = functools.partial(
        _router_kernel,
        k=k,
        n_experts=E,
        capacity=capacity,
        renormalize=renormalize,
    )
    out_shapes = (
        jax.ShapeDtypeStruct((T, k), jnp.int32),
        jax.ShapeDtypeStruct((T, k), jnp.int32),
        jax.ShapeDtypeStruct((T, k), jnp.float32),
        jax.ShapeDtypeStruct((T, k), bool),
    )
    blk = lambda ti: (ti, 0)
    return pl.pallas_call(
        kernel,
        grid=(nt,),
        in_specs=[pl.BlockSpec((block_t, E), blk)],
        out_specs=tuple(pl.BlockSpec((block_t, k), blk) for _ in range(4)),
        out_shape=out_shapes,
        scratch_shapes=[pltpu.VMEM((1, E), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=mode.interpret(),
        name="moe_router",
    )(logits)
