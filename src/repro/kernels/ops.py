"""Public jit'd wrappers for all kernels, with software/hardware dispatch.

Every op takes ``impl`` ∈ {"pallas", "ref"}: "ref" is the pure-jnp oracle
(the verified *software node*), "pallas" the TPU kernel (the *hardware
node*).  Models call these wrappers, so migrating a hot spot between the
two is a config flag — the paper's development story.

The pallas path compiles on a TPU and runs in TPU-interpret mode on the
CPU; ``repro.kernels.mode`` decides which.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import flash_attention_bwd as _fab
from repro.kernels import moe_dispatch as _moe
from repro.kernels import paged_attention as _pa
from repro.kernels import ref
from repro.kernels import rglru as _rglru
from repro.kernels import ssm_scan as _ssm

__all__ = [
    "attention",
    "paged_attention",
    "moe_router",
    "moe_dispatch",
    "moe_combine",
    "selective_scan",
    "gated_linear_scan",
    "aligned",
]


def aligned(dim: int, dtype=jnp.float32) -> bool:
    """True if ``dim`` is lane-aligned for full-speed TPU tiles."""
    del dtype
    return dim % 128 == 0


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    impl: str = "ref",
    block_q: int = 128,
    block_k: int = 128,
) -> jax.Array:
    if impl == "pallas":
        # differentiable: custom-VJP pairing the fwd kernel with the
        # blockwise dQ/dKV backward kernels (flash_attention_bwd.py)
        return _fab.flash_attention_vjp(
            q, k, v, causal, window, scale, block_q, block_k
        )
    if impl == "pallas_fwd":
        return _fa.flash_attention(
            q, k, v, causal=causal, window=window, scale=scale,
            block_q=block_q, block_k=block_k,
        )
    return ref.attention(q, k, v, causal=causal, window=window, scale=scale)


def paged_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_table: jax.Array,
    lengths: jax.Array,
    layer: Optional[jax.Array] = None,
    *,
    scale: Optional[float] = None,
    impl: str = "ref",
    pages_per_block: Optional[int] = None,
    block_b: Optional[int] = None,
) -> jax.Array:
    """Decode attention through a page table (the paged KV pool's compute
    side): q (B, Hq, D) against (P, T, Hkv, D) physical pages addressed by
    page_table (B, NP), masked at lengths (B,).  A 5-D pool
    (L, P, T, Hkv, D) is read at ``layer``, without a slice of it.

    ``pages_per_block``/``block_b`` tune the pallas kernel's DMA blocking
    (pages streamed per grid step / requests sharing a burst) — pure perf
    knobs, bit-identical output across settings; ignored by the oracle.
    """
    if impl == "pallas":
        return _pa.paged_attention(
            q, k_pages, v_pages, page_table, lengths, layer,
            scale=scale,
            pages_per_block=pages_per_block, block_b=block_b,
        )
    return ref.paged_attention(
        q, k_pages, v_pages, page_table, lengths, layer, scale=scale
    )


def moe_router(
    logits: jax.Array,
    *,
    k: int,
    capacity: int,
    renormalize: bool = True,
    impl: str = "ref",
    block_t: int = 256,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    if impl == "pallas":
        return _moe.moe_router(
            logits, k=k, capacity=capacity, renormalize=renormalize,
            block_t=block_t,
        )
    return ref.route_topk(logits, k=k, capacity=capacity, renormalize=renormalize)


# dispatch/combine are dense scatters/gathers; XLA handles them well and the
# GSPMD partitioner schedules the EP all-to-all.  They are thin and shared.
moe_dispatch = ref.moe_dispatch
moe_combine = ref.moe_combine


def selective_scan(
    x, dt, a, b, c, d, *, impl: str = "ref",
    block_d: int = 512, block_s: int = 128,
) -> jax.Array:
    if impl == "pallas":
        return _ssm.selective_scan(
            x, dt, a, b, c, d, block_d=block_d, block_s=block_s,
        )
    if impl == "chunked":
        return ref.selective_scan_chunked(x, dt, a, b, c, d, chunk=block_s)
    return ref.selective_scan(x, dt, a, b, c, d)


def gated_linear_scan(
    a, b, *, impl: str = "ref",
    block_d: int = 512, block_s: int = 128,
) -> jax.Array:
    if impl == "pallas":
        return _rglru.gated_linear_scan(
            a, b, block_d=block_d, block_s=block_s
        )
    if impl == "chunked":
        return ref.gated_linear_scan_chunked(a, b, chunk=block_s * 2)
    return ref.gated_linear_scan(a, b)
