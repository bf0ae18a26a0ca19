"""Pipeline parallelism over the pod axis (GPipe fill–drain).

The paper's two-level interconnect (on-chip network vs OCCC) maps to
intra-pod ICI vs inter-pod links; pipeline stages are the classic way to
put the *slow* link on the lowest-frequency traffic: one activation
transfer per microbatch per stage boundary instead of per-layer parameter
or gradient traffic.

Implementation: layers are already scan-stacked, so a stage is simply a
shard of the layer-stack dimension.  ``gpipe`` runs inside ``shard_map``
over the pipeline axis; stage boundaries are *split-phase* one-sided
neighbor puts through a :class:`~repro.core.engine.CommEngine`
(``engine.shift_nb`` — the software XLA node by default, the GAScore
Pallas node via ``engine=``): the activation put to stage s+1 is initiated
as soon as the stage body finishes, and the output bookkeeping of the
current tick overlaps the transfer (Extended-API comm/compute overlap at
the stage boundary).  Autodiff through the XLA engine's ppermute gives the
reverse-direction backward schedule for free; remat on the stage body
bounds activation memory.

Schedule (S stages, M microbatches, T = M + S - 1 ticks):

  tick t: stage s computes microbatch (t - s) if 0 <= t - s < M
          then puts its activation to stage s+1.

Bubble fraction = (S-1)/T, the standard GPipe overhead; the multi-pod
mesh uses S=2, M>=8 -> <= 11% bubble.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core.engine import CommEngine, XlaEngine, wait_all
from jax import shard_map

__all__ = ["gpipe", "pipelined"]


def gpipe(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    x_micro: jax.Array,  # (M, mb, ...) microbatched input (stage-0 content)
    *,
    axis: str,
    n_stages: int,
    broadcast_out: bool = True,
    engine: Optional[CommEngine] = None,
    boundary_segments: Optional[int] = None,
) -> jax.Array:
    """Run ``stage_fn`` as a GPipe pipeline inside shard_map over ``axis``.

    Every device holds ``stage_params`` for ITS stage (layer-stack shard).
    Returns the final-stage outputs (M, mb, ...).  With ``broadcast_out``
    the result is psum-broadcast to every stage (cheap relative to the
    steady-state activation traffic, and lets the loss epilogue run
    replicated); otherwise it is valid on the last stage only.

    ``engine`` is the stage-boundary transport (default: the software
    ``XlaEngine``; pass a ``GascoreEngine`` — or a heterogeneous
    ``EngineMap`` mixing software and hardware stages — to ship
    activations with the Pallas remote-DMA kernels; forward only, the
    Pallas path defines no VJP).  On engines with partial-permute support
    (``engine.can_permute_partial``) the boundary put is a chain permute
    (s -> s+1, no wrap — no dead traffic); bijection-only transports
    (GAScore: every recv semaphore signalled exactly once) use a ring
    ``Shift(1)`` whose wrap edge (S-1 -> 0) is dead: stage 0 always
    injects fresh microbatches and ignores its carry.

    The boundary transport is *plan-driven*: ``repro.core.sched.plan_p2p``
    sizes ``boundary_segments`` from the activation bytes and the engine
    cost model (pass it explicitly to pin); with >1 segments the
    activation ships as multiple puts in flight, so the wire overlaps the
    per-tick output bookkeeping.
    """
    S = n_stages
    M = x_micro.shape[0]
    eng = engine or XlaEngine(axis, S)
    chain = tuple(range(1, S)) + (None,)  # s -> s+1, last stage sends nowhere
    use_chain = eng.can_permute_partial
    if boundary_segments is None:
        from repro.core import sched

        mb_bytes = math.prod(x_micro.shape[1:]) * x_micro.dtype.itemsize
        boundary_segments = sched.plan_p2p(nbytes=mb_bytes, engine=eng).n_segments
    n_seg = max(1, int(boundary_segments))

    def _one_put_nb(y):
        return eng.permute_nb(y, chain) if use_chain else eng.shift_nb(y, 1)

    def boundary_put_nb(y):
        """Initiate the stage-boundary put as n_seg in-flight segments."""
        if n_seg == 1 or y.ndim == 0 or y.shape[0] < n_seg:
            return [_one_put_nb(y)]
        from repro.core.collectives import segment_bounds

        return [
            _one_put_nb(lax.slice_in_dim(y, lo, hi, axis=0))
            for lo, hi in segment_bounds(y.shape[0], n_seg)
        ]

    def boundary_wait(pendings):
        parts = wait_all(pendings)
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)

    stage = eng.my_id()
    mb_shape = x_micro.shape[1:]
    carry_in = jnp.zeros(mb_shape, x_micro.dtype)
    outputs = jnp.zeros_like(x_micro)

    for t in range(M + S - 1):
        # stage 0 injects microbatch t; others consume the neighbor put
        mb_idx = jnp.clip(t - stage, 0, M - 1)
        inject = lax.dynamic_index_in_dim(x_micro, jnp.clip(t, 0, M - 1), 0,
                                          keepdims=False)
        x_in = jnp.where(stage == 0, inject, carry_in)
        active = (t - stage >= 0) & (t - stage < M)
        y = stage_fn(stage_params, x_in)
        y = jnp.where(active, y, jnp.zeros_like(y))
        # split-phase put of activations to the next stage: initiate as
        # soon as y exists, record outputs while the transfer is in flight
        pendings = boundary_put_nb(y)
        # last stage records its result (overlaps the boundary put)
        outputs = lax.cond(
            active & (stage == S - 1),
            lambda o: lax.dynamic_update_index_in_dim(o, y, mb_idx, 0),
            lambda o: o,
            outputs,
        )
        carry_in = boundary_wait(pendings)
    if broadcast_out:
        outputs = lax.psum(outputs, axis)  # only the last stage is nonzero
    return outputs


def pipelined(
    stage_fn: Callable,
    *,
    mesh: jax.sharding.Mesh,
    axis: str = "pod",
    n_micro: int,
    params_spec: Any,
    x_spec: P,
    out_spec: Optional[P] = None,
    remat: bool = True,
    engine: Optional[CommEngine] = None,
    boundary_segments: Optional[int] = None,
) -> Callable:
    """Wrap a stage function into a jit-able pipelined forward.

    ``params_spec`` must shard the layer-stack dimension over ``axis``;
    ``x_spec``/``out_spec`` shard the microbatch dimension over nothing
    (microbatches stream through stages, data-parallel axes can shard the
    per-microbatch batch dim as usual).  ``engine`` selects the
    stage-boundary transport and ``boundary_segments`` its segmentation
    (default: planned from the activation size, see :func:`gpipe`).
    """
    n_stages = mesh.shape[axis]
    body = jax.checkpoint(stage_fn) if remat else stage_fn

    def fn(stage_params, x_micro):
        return gpipe(
            body, stage_params, x_micro, axis=axis, n_stages=n_stages,
            engine=engine, boundary_segments=boundary_segments,
        )

    return shard_map(
        fn,
        mesh=mesh,
        in_specs=(params_spec, x_spec),
        out_specs=out_spec if out_spec is not None else x_spec,
        check_vma=False,
    )
