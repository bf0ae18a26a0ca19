"""RG-LRU gated diagonal linear recurrence (recurrentgemma), fused for TPU.

  h_t = a_t ⊙ h_{t-1} + sqrt(1 - a_t²) ⊙ (i_t ⊙ x_t)

with a_t already materialized by the block (a_t = exp(-c·softplus(Λ)·r_t)).
The kernel takes the generic form ``h_t = a_t ⊙ h_{t-1} + b_t`` so it
doubles as a fused scan for any diagonal gated recurrence; the RG-LRU
gating algebra lives in the model layer (it is elementwise and fuses there).

Grid: (batch, D/block_d, S/block_s), time sequential, state in VMEM scratch.
Oracle: ``repro.kernels.ref.gated_linear_scan`` (lax.scan / associative_scan).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import mode

__all__ = ["gated_linear_scan"]


def _lru_kernel(a_ref, b_ref, y_ref, h_scr, *, block_s: int):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    def step(t, h):
        at = a_ref[0, t].astype(jnp.float32)
        bt = b_ref[0, t].astype(jnp.float32)
        h = at * h + bt
        y_ref[0, t] = h.astype(y_ref.dtype)
        return h

    h = lax.fori_loop(0, block_s, step, h_scr[0])
    h_scr[0, :] = h


@functools.partial(
    jax.jit, static_argnames=("block_d", "block_s")
)
def gated_linear_scan(
    a: jax.Array,
    b: jax.Array,
    *,
    block_d: int = 512,
    block_s: int = 128,
) -> jax.Array:
    """y_t = a_t * y_{t-1} + b_t along axis 1.

    Args:
      a, b: (B, S, D); ``a`` in [0, 1).
    Returns:
      (B, S, D) scan output in b.dtype.
    """
    B, S, D = a.shape
    block_d = min(block_d, D)
    block_s = min(block_s, S)
    if D % block_d or S % block_s:
        raise ValueError(f"(S={S}, D={D}) not divisible by ({block_s},{block_d})")
    nd, ns = D // block_d, S // block_s

    kernel = functools.partial(_lru_kernel, block_s=block_s)
    spec = pl.BlockSpec((1, block_s, block_d), lambda bi, di, si: (bi, si, di))
    return pl.pallas_call(
        kernel,
        grid=(B, nd, ns),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((B, S, D), b.dtype),
        scratch_shapes=[pltpu.VMEM((1, block_d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=mode.interpret(),
        name="rglru_gated_linear_scan",
    )(a, b)
