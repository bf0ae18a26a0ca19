"""Paged decode-attention kernel for TPU: K/V read through a page table.

The serving hot spot once the KV cache is page-granular (the global KV
pool of ``repro.serving.pool``): each request's cache is a list of
fixed-size token pages scattered through one physical pool array, and the
decode step must attend over them *in place* — no dense gather, no
per-request contiguous copy.

Serving-grade blocking (the SMI lesson: decouple message granularity
from transfer granularity — here, page granularity from kernel-grid
granularity):

- **Batch blocking** — the grid is ``(B/BLOCK_B, NP/PAGES_PER_BLOCK)``,
  so one kernel program serves ``BLOCK_B`` requests at once and their
  page DMAs are issued as one burst per block instead of one grid step
  per (request, page).
- **Page-block streaming** — K/V stay in HBM (``memory_space=ANY``); the
  kernel resolves physical pages through the scalar-prefetched table and
  copies ``BLOCK_B x PAGES_PER_BLOCK`` whole pages (every KV head) per
  grid step into VMEM scratch with explicit ``make_async_copy`` DMAs —
  the hardware analogue of the pool's one-sided vectored ``get_nbv`` page
  fetch, one level down the memory hierarchy.  A page is copied whole
  because HBM tiles its ``(Hkv, D)`` dims: one head of a bf16 page is not
  a tile-aligned slice.
- **Double buffering** — two VMEM slots: the next page block's DMA burst
  is issued *before* the current block's compute, so the wire hides
  behind the online-softmax work exactly like the split-phase GASNet
  ops hide behind the decode step.

The pool may hold every layer, ``(L, P, T, Hkv, D)``: a scalar-prefetched
layer index picks the layer inside the DMAs, so a decode step that
carries the whole pool through its layer loop hands it over as it is and
no per-layer slice of it is ever made.

All heads of a request are scored in one matmul: the page is viewed as
``(T * Hkv, D)`` rows, every query head is scored against every row, and
rows of other KV heads are masked like positions past the length.  The
masked products are exact zeros, so each head's softmax and value sum
are over its own rows only.

Blocking is a pure perf knob, never a semantics knob: the per-request
online-softmax update is computed page by page in logical order with
shapes independent of ``BLOCK_B``/``PAGES_PER_BLOCK``, so the output is
bit-identical across block settings (property-tested in
``tests/test_properties.py``).

Positions past ``lengths[b]`` are masked *before* the running max and V
is zeroed at masked positions, so padded page-table entries may point at
any physical page — even one holding NaN garbage.

Oracle: ``repro.kernels.ref.paged_attention``.  Compiled on a TPU,
interpreted on the CPU (``repro.kernels.mode``).
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

__all__ = ["paged_attention", "DEFAULT_PAGES_PER_BLOCK", "DEFAULT_BLOCK_B"]

NEG_INF = -1e30

# Default blocking: 4 requests share each DMA burst, 4 pages stream per
# grid step (tuned for decode shapes where pages are small and the grid
# overhead of one-(request, page)-per-step dominates).
DEFAULT_BLOCK_B = 4
DEFAULT_PAGES_PER_BLOCK = 4


def _pa_kernel(
    table_ref,  # scalar prefetch: (B * NP,) physical page ids
    len_ref,  # scalar prefetch: (B,) live lengths
    layer_ref,  # scalar prefetch: (1,) the layer of the pool to read
    q_ref,  # (BLOCK_B, Hq, D)
    k_hbm,  # (L, P, T, Hkv, D) — full pool, memory_space=ANY
    v_hbm,  # (L, P, T, Hkv, D)
    o_ref,  # (BLOCK_B, Hq, D)
    k_buf,  # VMEM (2, BLOCK_B, PPB, T, Hkv, D) double-buffered page blocks
    v_buf,
    sems,  # DMA semaphores (2, 2, BLOCK_B, PPB)
    m_scr,  # (BLOCK_B, Hq)
    l_scr,
    acc_scr,  # (BLOCK_B, Hq, D)
    *,
    scale: float,
    page_tokens: int,
    n_pages: int,
    block_b: int,
    pages_per_block: int,
    group: int,
):
    bb = pl.program_id(0)
    pb = pl.program_id(1)
    npb = pl.num_programs(1)
    T = page_tokens
    Hkv = k_buf.shape[4]
    layer = layer_ref[0]

    def issue(slot, blk):
        """One DMA burst: every (request, page) of one page block."""
        for i in range(block_b):
            gb = bb * block_b + i
            for j in range(pages_per_block):
                # clamp ragged tails: the copied page is fully masked
                gp = jnp.minimum(blk * pages_per_block + j, n_pages - 1)
                page = table_ref[gb * n_pages + gp]
                pltpu.make_async_copy(
                    k_hbm.at[layer, page], k_buf.at[slot, i, j],
                    sems.at[0, slot, i, j],
                ).start()
                pltpu.make_async_copy(
                    v_hbm.at[layer, page], v_buf.at[slot, i, j],
                    sems.at[1, slot, i, j],
                ).start()

    def wait(slot):
        for i in range(block_b):
            for j in range(pages_per_block):
                pltpu.make_async_copy(
                    k_hbm.at[0, 0], k_buf.at[slot, i, j],
                    sems.at[0, slot, i, j],
                ).wait()
                pltpu.make_async_copy(
                    v_hbm.at[0, 0], v_buf.at[slot, i, j],
                    sems.at[1, slot, i, j],
                ).wait()

    @pl.when(pb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        issue(0, 0)

    @pl.when(pb + 1 < npb)
    def _prefetch():
        # next block's wire time hides behind this block's compute
        issue((pb + 1) % 2, pb + 1)

    slot = pb % 2
    wait(slot)

    R = T * Hkv  # rows of one page: token-major, head-minor
    Hq = q_ref.shape[1]
    # score column c is token c // Hkv of KV head c % Hkv; query head r
    # reads KV head r // group
    col = lax.broadcasted_iota(jnp.int32, (Hq, R), 1)
    same_head = lax.broadcasted_iota(jnp.int32, (Hq, R), 0) // group == (
        col % Hkv
    )
    col_tok = col // Hkv
    row_tok = lax.broadcasted_iota(jnp.int32, (R, k_buf.shape[5]), 0) // Hkv
    for i in range(block_b):
        gb = bb * block_b + i
        q = q_ref[i].astype(jnp.float32) * scale  # (Hq, D)
        m_prev = m_scr[i, :]
        l_prev = l_scr[i, :]
        acc = acc_scr[i]
        # pages combine in logical order with BLOCK-INDEPENDENT shapes:
        # bit-identical across (block_b, pages_per_block) settings
        for j in range(pages_per_block):
            gp = pb * pages_per_block + j
            k = k_buf[slot, i, j].astype(jnp.float32).reshape(R, -1)
            v = v_buf[slot, i, j].astype(jnp.float32).reshape(R, -1)
            s = lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (Hq, R)
            mask = same_head & (gp * T + col_tok < len_ref[gb])
            # mask BEFORE the running max and zero V at masked slots:
            # garbage (even NaN) in padded pages never reaches the output
            s = jnp.where(mask, s, NEG_INF)
            v = jnp.where(gp * T + row_tok < len_ref[gb], v, 0.0)
            m_new = jnp.maximum(m_prev, s.max(axis=-1))
            pexp = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_prev = alpha * l_prev + pexp.sum(axis=-1)
            acc = acc * alpha[:, None] + lax.dot_general(
                pexp, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_prev = m_new
        m_scr[i, :] = m_prev
        l_scr[i, :] = l_prev
        acc_scr[i] = acc

    @pl.when(pb == npb - 1)
    def _finalize():
        for i in range(block_b):
            l = l_scr[i, :]
            denom = jnp.where(l == 0.0, 1.0, l)
            o_ref[i] = (acc_scr[i] / denom[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "pages_per_block", "block_b"),
)
def paged_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_table: jax.Array,
    lengths: jax.Array,
    layer: Optional[jax.Array] = None,
    *,
    scale: Optional[float] = None,
    pages_per_block: Optional[int] = None,
    block_b: Optional[int] = None,
) -> jax.Array:
    """Decode attention over a paged KV pool.

    Args:
      q: (B, Hq, D) — one query token per request (decode step).
      k_pages, v_pages: (P, T, Hkv, D) — the physical page pool; or the
        pools of every layer, (L, P, T, Hkv, D), of which the kernel's
        DMAs read layer ``layer`` alone (no slice of the pool is made).
      page_table: (B, NP) int32 — physical page id of request b's logical
        page p; entries at or past ``ceil(lengths[b] / T)`` are masked and
        may hold any valid physical id (even pages holding garbage).
      lengths: (B,) int32 — live cache positions per request.
      layer: int32 scalar, the layer to read of a 5-D pool; given with a
        5-D pool and only then.
      pages_per_block: physical pages streamed per grid step (default
        ``DEFAULT_PAGES_PER_BLOCK``, clamped to NP).  Perf knob only —
        the output is bit-identical across settings.
      block_b: requests sharing one DMA burst (default
        ``DEFAULT_BLOCK_B``, clamped to B).  Perf knob only.
    Returns:
      (B, Hq, D) in q.dtype.
    """
    if k_pages.ndim == 4:
        if layer is not None:
            raise ValueError("layer is given only with a 5-D pool")
        # a pool of one layer: a leading axis of 1 is a free reshape
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    elif k_pages.ndim != 5 or layer is None:
        raise ValueError(
            f"k_pages must be (P, T, Hkv, D), or (L, P, T, Hkv, D) with a "
            f"layer; got {k_pages.shape} with layer {layer!r}"
        )
    B, Hq, D = q.shape
    _, P, T, Hkv, Dk = k_pages.shape
    if Dk != D:
        raise ValueError(f"head_dim mismatch: q has {D}, pages have {Dk}")
    if k_pages.shape != v_pages.shape:
        raise ValueError(
            f"k_pages {k_pages.shape} != v_pages {v_pages.shape}"
        )
    if Hq % Hkv != 0:
        raise ValueError(f"Hq={Hq} not a multiple of Hkv={Hkv}")
    if page_table.shape[0] != B or lengths.shape != (B,):
        raise ValueError("page_table/lengths batch mismatch")
    group = Hq // Hkv
    NP = page_table.shape[1]
    if scale is None:
        scale = 1.0 / (D**0.5)
    ppb = max(1, min(pages_per_block or DEFAULT_PAGES_PER_BLOCK, NP))
    blk_b = max(1, min(block_b or DEFAULT_BLOCK_B, B))
    npb = -(-NP // ppb)

    # pad the batch to a multiple of the block; padded rows have length 0
    # (their output is zeros and dropped below) and table entries 0
    Bp = -(-B // blk_b) * blk_b
    table = page_table.astype(jnp.int32)
    lens = lengths.astype(jnp.int32)
    qq = q
    if Bp != B:
        qq = jnp.pad(q, ((0, Bp - B), (0, 0), (0, 0)))
        table = jnp.pad(table, ((0, Bp - B), (0, 0)))
        lens = jnp.pad(lens, ((0, Bp - B),))

    kernel = functools.partial(
        _pa_kernel,
        scale=scale,
        page_tokens=T,
        n_pages=NP,
        block_b=blk_b,
        pages_per_block=ppb,
        group=group,
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(Bp // blk_b, npb),
            in_specs=[
                pl.BlockSpec(
                    (blk_b, Hq, D), lambda b, p, table, lens, lyr: (b, 0, 0)
                ),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(
                (blk_b, Hq, D), lambda b, p, table, lens, lyr: (b, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((2, blk_b, ppb, T, Hkv, D), k_pages.dtype),
                pltpu.VMEM((2, blk_b, ppb, T, Hkv, D), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2, blk_b, ppb)),
                pltpu.VMEM((blk_b, Hq), jnp.float32),
                pltpu.VMEM((blk_b, Hq), jnp.float32),
                pltpu.VMEM((blk_b, Hq, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((Bp, Hq, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=mode.interpret(),
        name="paged_attention_decode",
    )(table.reshape(-1), lens, jnp.asarray(layer, jnp.int32).reshape(1),
      qq, k_pages, v_pages)
    return out[:B]
