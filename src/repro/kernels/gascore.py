"""GAScore: the paper's remote-DMA engine, as Pallas TPU kernels.

The paper's GAScore is a hardware block that executes one-sided remote
memory operations (Active-Message packets) between nodes, driven by
GASNet-argument command words.  The exact TPU analogue is an inter-chip
DMA over ICI: ``pltpu.make_async_remote_copy`` builds the DMA descriptor
(source ref, *remote* destination ref, destination chip) and a pair of DMA
semaphores provides the send-complete / receive-complete notifications that
the paper delivers via its handler mechanism.

Kernels:

- :func:`ring_shift`          — one put to node ``(me + k) % n``.
- :func:`perm_put`            — one put along an arbitrary static permutation.
- :func:`offset_put`          — put with *sender-chosen remote offset*
                                (AMLong: the sender addresses remote memory
                                directly; purest GAScore semantics).
- :func:`ring_all_gather`     — n-1 chained puts, each forwarding the chunk
                                received on the previous hop (single fused
                                kernel; compute proceeds between start/wait).
- :func:`ring_reduce_scatter` — n-1 chained put+accumulate hops.

All kernels compile to Mosaic for real ICI on a TPU and run in TPU
interpret mode on the CPU (``repro.kernels.mode``).  They must be invoked
inside a ``shard_map`` over ``axis``; the node axis must be the kernel's
only mesh axis (1-D subgrid), which is how the GAS layer always invokes
them.

Every kernel opens with a barrier on the global barrier semaphore
(``collective_id`` set): a remote DMA writes into a buffer of the peer's
instance of the same kernel, so no node may write before every node has
entered it.  Without the handshake a fast node's put can land in a peer
buffer that an earlier program on that peer still owns.

VMEM/alignment notes (target hardware): chunks are staged through VMEM
scratch; callers should keep the trailing dim a multiple of 128 and the
second-minor a multiple of 8 (f32) / 16 (bf16) for full-speed DMAs — the
``ops.aligned`` helper checks this.  The pure-jnp oracles live in
``repro.kernels.ref``.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import mode

__all__ = [
    "ring_shift",
    "perm_put",
    "offset_put",
    "ring_all_gather",
    "ring_reduce_scatter",
]


def _params() -> pltpu.CompilerParams:
    return pltpu.CompilerParams(collective_id=0)


def _enter(axis: str, n_nodes: int) -> None:
    """Barrier at kernel entry: signal every peer, wait for all of them
    (see the module docstring)."""
    sem = pltpu.get_barrier_semaphore()
    me = lax.axis_index(axis)
    for d in range(1, n_nodes):
        pltpu.semaphore_signal(
            sem, 1, device_id=(lax.rem(me + d, n_nodes),),
            device_id_type=pltpu.DeviceIdType.MESH,
        )
    pltpu.semaphore_wait(sem, n_nodes - 1)


def _any_spec() -> pl.BlockSpec:
    return pl.BlockSpec(memory_space=pl.ANY)


def _hbm(x: jax.Array) -> jax.Array:
    """``x`` placed in HBM for a kernel operand (see :func:`_hbm_out`)."""
    return pltpu.with_memory_space_constraint(x, pltpu.HBM)


def _hbm_out(shape, dtype):
    """A kernel result placed in HBM.  A remote DMA writes the peer's
    buffer at the address it has in the peer's instance of the kernel;
    left to XLA, small buffers go to VMEM, where a put from a peer does
    not arrive (the AM flags came back all zero on four v5e chips)."""
    return pltpu.HBM(shape, dtype)


# --------------------------------------------------------------------------- #
# point-to-point puts
# --------------------------------------------------------------------------- #
def ring_shift(
    x: jax.Array, *, k: int, axis: str, n_nodes: int
) -> jax.Array:
    """Every node's ``x`` lands on node ``(me + k) % n`` (one remote DMA)."""
    k = k % n_nodes
    if k == 0:
        return x
    if x.ndim == 0:  # Mosaic blocks have rank >= 1
        return ring_shift(x[None], k=k, axis=axis, n_nodes=n_nodes)[0]
    if x.dtype == jnp.bool_:  # Mosaic DMAs do not move bools
        return ring_shift(
            x.astype(jnp.int32), k=k, axis=axis, n_nodes=n_nodes
        ) != 0

    def kernel(x_ref, o_ref, send_sem, recv_sem):
        _enter(axis, n_nodes)
        me = lax.axis_index(axis)
        dst = lax.rem(me + k, n_nodes)
        rdma = pltpu.make_async_remote_copy(
            src_ref=x_ref,
            dst_ref=o_ref,
            send_sem=send_sem,
            recv_sem=recv_sem,
            device_id=(dst,),
            device_id_type=pltpu.DeviceIdType.MESH,
        )
        rdma.start()
        rdma.wait()

    return pl.pallas_call(
        kernel,
        out_shape=_hbm_out(x.shape, x.dtype),
        in_specs=[_any_spec()],
        out_specs=_any_spec(),
        scratch_shapes=[pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA],
        interpret=mode.interpret(),
        compiler_params=_params(),
        name=f"gascore_ring_shift_{k}",
    )(_hbm(x))


def perm_put(
    x: jax.Array,
    *,
    dst: Tuple[int, ...],
    axis: str,
    n_nodes: int,
) -> jax.Array:
    """Put along a static permutation: node i's ``x`` lands on ``dst[i]``.

    ``dst`` must be a bijection of 0..n-1 (every node receives exactly one
    message, so its recv semaphore is signalled exactly once).  The XLA
    engine additionally supports non-bijective patterns; the GAScore engine
    mirrors hardware, where an unpaired wait would deadlock.
    """
    if sorted(dst) != list(range(n_nodes)):
        raise ValueError(f"perm_put requires a bijection, got {dst}")
    if x.ndim == 0:  # Mosaic blocks have rank >= 1
        return perm_put(x[None], dst=dst, axis=axis, n_nodes=n_nodes)[0]
    if x.dtype == jnp.bool_:  # Mosaic DMAs do not move bools
        return perm_put(
            x.astype(jnp.int32), dst=dst, axis=axis, n_nodes=n_nodes
        ) != 0

    def kernel(dst_ref, x_ref, o_ref, send_sem, recv_sem):
        _enter(axis, n_nodes)
        me = lax.axis_index(axis)
        target = dst_ref[me]
        rdma = pltpu.make_async_remote_copy(
            src_ref=x_ref,
            dst_ref=o_ref,
            send_sem=send_sem,
            recv_sem=recv_sem,
            device_id=(target,),
            device_id_type=pltpu.DeviceIdType.MESH,
        )
        rdma.start()
        rdma.wait()

    dst_arr = jnp.asarray(dst, jnp.int32)
    return pl.pallas_call(
        kernel,
        out_shape=_hbm_out(x.shape, x.dtype),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            _any_spec(),
        ],
        out_specs=_any_spec(),
        scratch_shapes=[pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA],
        interpret=mode.interpret(),
        compiler_params=_params(),
        name="gascore_perm_put",
    )(dst_arr, _hbm(x))


def offset_put(
    seg: jax.Array,
    data: jax.Array,
    offset: jax.Array,
    *,
    k: int,
    axis: str,
    n_nodes: int,
) -> jax.Array:
    """AMLong via GAScore: write ``data`` into the partition of node
    ``(me + k) % n`` of segment ``seg`` at *sender-chosen* element offset
    ``offset`` along the leading axis.

    The sender constructs the full remote address (``dst_ref`` slice) in the
    DMA descriptor — this is precisely the GAScore command format (local
    address, remote node, remote address, length).  The updated segment is
    returned (aliased in-place on TPU).

    ``seg``: (S, ...) local partition; ``data``: (L, ...) with L <= S and
    matching trailing dims; ``offset``: scalar int32, 0 <= offset <= S - L.
    """
    k = k % n_nodes
    L = data.shape[0]

    def kernel(off_ref, data_ref, seg_in_ref, seg_ref, send_sem, recv_sem):
        del seg_in_ref  # aliased with seg_ref; content already in place
        _enter(axis, n_nodes)
        me = lax.axis_index(axis)
        dst = lax.rem(me + k, n_nodes)
        rdma = pltpu.make_async_remote_copy(
            src_ref=data_ref,
            dst_ref=seg_ref.at[pl.ds(off_ref[0], L)],
            send_sem=send_sem,
            recv_sem=recv_sem,
            device_id=(dst,),
            device_id_type=pltpu.DeviceIdType.MESH,
        )
        rdma.start()
        rdma.wait()

    off_arr = jnp.asarray(offset, jnp.int32).reshape((1,))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(seg.shape, seg.dtype),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            _any_spec(),
            _any_spec(),
        ],
        out_specs=_any_spec(),
        input_output_aliases={2: 0},
        scratch_shapes=[pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA],
        interpret=mode.interpret(),
        compiler_params=_params(),
        name=f"gascore_offset_put_{k}",
    )(off_arr, _hbm(data), seg)  # constraining seg breaks the alias


# --------------------------------------------------------------------------- #
# fused ring collectives
# --------------------------------------------------------------------------- #
def ring_all_gather(
    x: jax.Array, *, axis: str, n_nodes: int
) -> jax.Array:
    """All-gather: local chunk (m, ...) -> tiled (n*m, ...).

    One fused kernel performs all n-1 hops: at hop h every node forwards
    the chunk it received at hop h-1 (chunk id ``me - h``) to its right
    neighbor, writing directly into the neighbor's output slot with a
    single remote DMA — no intermediate staging, which is the bandwidth
    advantage the paper claims for hardware-managed RDMA.
    """
    n = n_nodes
    chunk_shape = x.shape

    def kernel(x_ref, o_ref, local_sem, send_sems, recv_sems):
        _enter(axis, n_nodes)
        me = lax.axis_index(axis)
        right = lax.rem(me + 1, n)
        # publish my own chunk into my slot (local DMA)
        lcopy = pltpu.make_async_copy(x_ref, o_ref.at[me], local_sem)
        lcopy.start()
        lcopy.wait()

        def hop(h, _):
            slot = lax.rem(me - h + n + n, n)  # chunk forwarded at hop h+1
            # Per-hop semaphores: a fast neighbor may start hop h+1 before
            # we finish hop h; sharing one DMA semaphore would let its
            # arrival satisfy our hop-h wait while hop-h bytes are still in
            # flight (observed as NaN slots in interpret mode).
            rdma = pltpu.make_async_remote_copy(
                src_ref=o_ref.at[slot],
                dst_ref=o_ref.at[slot],
                send_sem=send_sems.at[h],
                recv_sem=recv_sems.at[h],
                device_id=(right,),
                device_id_type=pltpu.DeviceIdType.MESH,
            )
            rdma.start()
            rdma.wait()
            return 0

        lax.fori_loop(0, n - 1, hop, 0)

    out = pl.pallas_call(
        kernel,
        out_shape=_hbm_out((n,) + chunk_shape, x.dtype),
        in_specs=[_any_spec()],
        out_specs=_any_spec(),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA((n - 1,)),
            pltpu.SemaphoreType.DMA((n - 1,)),
        ],
        interpret=mode.interpret(),
        compiler_params=_params(),
        name="gascore_ring_all_gather",
    )(_hbm(x))
    return out.reshape((n * chunk_shape[0],) + chunk_shape[1:])


def ring_reduce_scatter(
    x: jax.Array, *, axis: str, n_nodes: int
) -> jax.Array:
    """Reduce-scatter: (n*m, ...) -> summed local chunk (m, ...).

    The packet for chunk ``c`` starts at node ``c+1`` and travels the ring
    accumulating each visited node's contribution (see
    ``core.collectives.ring_reduce_scatter`` for the schedule proof).  The
    accumulation happens in VMEM between the recv-wait of hop h and the
    send of hop h+1 — GAScore's "handler runs on arrival" realized as a
    fused add.
    """
    n = n_nodes
    if x.shape[0] % n != 0:
        raise ValueError(f"dim0 {x.shape[0]} not divisible by {n}")
    m = x.shape[0] // n
    chunk_shape = (m,) + x.shape[1:]
    xb = x.reshape((n,) + chunk_shape)

    def kernel(x_ref, o_ref, acc, recv2, mine, csem, send_sems, recv_sems):
        _enter(axis, n_nodes)
        me = lax.axis_index(axis)
        right = lax.rem(me + 1, n)
        # seed: my contribution to chunk (me - 1) mod n
        seed = pltpu.make_async_copy(
            x_ref.at[lax.rem(me - 1 + n, n)], acc, csem
        )
        seed.start()
        seed.wait()

        def hop(h, _):
            # Ship the partial sum to the right neighbor.  Per-hop
            # semaphores + ping-pong recv buffers: the neighbor's hop h+1
            # write may land while we still read hop h's packet; it goes to
            # the other recv slot.  It cannot run 2 hops ahead because its
            # hop h+2 send waits on our hop h+1 put, which we only issue
            # after consuming recv slot h%2.
            slot = lax.rem(h - 1, 2)
            rdma = pltpu.make_async_remote_copy(
                src_ref=acc,
                dst_ref=recv2.at[slot],
                send_sem=send_sems.at[h - 1],
                recv_sem=recv_sems.at[h - 1],
                device_id=(right,),
                device_id_type=pltpu.DeviceIdType.MESH,
            )
            rdma.start()
            rdma.wait()
            # the packet now here is for chunk c = me - h - 1 (h is 1-based)
            c = lax.rem(me - h - 1 + 2 * n, n)
            fetch = pltpu.make_async_copy(x_ref.at[c], mine, csem)
            fetch.start()
            fetch.wait()
            acc[...] = recv2[slot] + mine[...]
            return 0

        lax.fori_loop(1, n, hop, 0, unroll=False)
        out = pltpu.make_async_copy(acc, o_ref, csem)
        out.start()
        out.wait()

    out = pl.pallas_call(
        kernel,
        out_shape=_hbm_out(chunk_shape, x.dtype),
        in_specs=[_any_spec()],
        out_specs=_any_spec(),
        scratch_shapes=[
            pltpu.VMEM(chunk_shape, x.dtype),
            pltpu.VMEM((2,) + chunk_shape, x.dtype),
            pltpu.VMEM(chunk_shape, x.dtype),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA((n - 1,)),
            pltpu.SemaphoreType.DMA((n - 1,)),
        ],
        interpret=mode.interpret(),
        compiler_params=_params(),
        name="gascore_ring_reduce_scatter",
    )(_hbm(xb))
    return out
