"""Quickstart: the GASNet-style PGAS API in five minutes.

Eight "nodes" (CPU host devices standing in for TPU chips), one partitioned
global address space, one-sided puts/gets — blocking (Core API) and
split-phase non-blocking (Extended API) with comm/compute overlap — Active
Messages with handlers, and a ring all-reduce built from neighbor puts —
the paper's programming model end to end.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import collectives, gasnet
from repro.launch.mesh import make_mesh

N = 8
mesh = make_mesh((N,), ("node",))
ctx = gasnet.Context(mesh, node_axis="node", backend="xla",
                     am_payload_width=4)

# --- 1. attach a segment: every node owns a 64-float partition -----------
aspace = ctx.address_space()
aspace.register("scratch", (64,), jnp.float32)
seg = aspace.alloc("scratch")
print(f"segment 'scratch': {N} nodes x {aspace.spec('scratch').local_shape}")

# --- 2. one-sided put: write into your right neighbor's memory -----------
def put_demo(node, seg):
    payload = jnp.full((4,), 10.0 + node.my_id, jnp.float32)
    seg = node.put(seg, payload, to=gasnet.Shift(1), index=8)
    node.barrier()
    return seg

seg = ctx.spmd(put_demo, seg)
print("after put, node 3 holds (from node 2):",
      np.asarray(seg)[3, 8:12])

# --- 3. one-sided get: read 4 floats from node (me+2) --------------------
def get_demo(node, seg):
    return node.get(seg, frm=gasnet.Shift(2), index=8, size=4)[None]

got = ctx.spmd(get_demo, seg, out_specs=P("node"))
print("node 0 got (from node 2):", np.asarray(got)[0])

# --- 4. Extended API: split-phase non-blocking put/get with overlap -------
# put_nb initiates the transfer and returns a handle; independent compute
# issued before node.sync(h) overlaps the wire (gasnet_put_nb semantics).
def overlap_demo(node, seg):
    h = node.put_nb(seg, jnp.full((4,), 70.0 + node.my_id, jnp.float32),
                    to=gasnet.Shift(1), index=20)
    local = node.local(seg)[:16]
    stat = jnp.tanh(local) @ jnp.ones((16,))     # overlaps the transfer
    seg = node.sync(h)                           # split-phase completion
    g = node.get_nb(seg, frm=gasnet.Shift(1), index=20, size=4)
    fetched = node.sync(g)                       # completes the get
    return seg, (fetched + 0.0 * stat)[None]

seg, fetched = ctx.spmd(overlap_demo, seg, out_specs=(P("node"), P("node")))
print("node 0 put_nb'd to node 1, then get_nb'd it back:",
      np.asarray(fetched)[0])

# --- 5. Active Messages: handler runs at the receiver ---------------------
@ctx.handlers.handler("accumulate")
def h_acc(state, payload, args):
    out = dict(state)
    out["acc"] = state["acc"] + payload.sum() * args[0]
    return out

def am_demo(node, seg):
    state = {"acc": jnp.zeros((), jnp.float32)}
    dest = jnp.asarray((node.my_id + 3) % N, jnp.int32)
    node.am_medium(dest, "accumulate",
                   payload=jnp.ones((4,), jnp.float32), args=(2,))
    state = node.am_flush(state)  # route + run handlers
    return state["acc"][None]

acc = ctx.spmd(am_demo, seg, out_specs=P("node"))
print("AM handler results (each node got one message, 4*1*2):",
      np.asarray(acc))

# --- 6. collectives from one-sided puts (incl. broadcast + exchange) -------
# All rings are built on the split-phase primitives internally: each hop's
# put is initiated before the previous hop's local work.
def coll_demo(node, x):
    e = node.engine
    ar = collectives.ring_all_reduce(e, node.local(x))
    bc = collectives.broadcast(e, node.local(x), root=2)
    ex = collectives.exchange(e, node.local(x))  # all-to-all, fully in flight
    return ar[None], bc[None], ex[None]

x = jnp.arange(float(N * 16)).reshape(N, 16)
ar, bc, ex = ctx.spmd(coll_demo, x, out_specs=(P("node"),) * 3)
assert np.allclose(np.asarray(ar)[0], np.asarray(x).sum(0))
assert np.allclose(np.asarray(bc)[5], np.asarray(x)[2])
print("ring all-reduce / broadcast / exchange over one-sided puts: OK")

# --- 7. the collective scheduler: size-aware plans + segmented rings -------
# sched picks the algorithm (tree / recursive doubling / segmented ring)
# from payload bytes, node count, and the engine cost model, then runs it.
from repro.core import sched

for size in (1 << 10, 1 << 20, 1 << 24):  # 1 KiB, 1 MiB, 16 MiB
    print(" ", sched.plan_collective("all_reduce", nbytes=size,
                                     n_nodes=N).describe())

def seg_demo(node, x):  # segmented ring all-reduce, 4 slices x depth 2
    return collectives.segmented_ring_all_reduce(
        node.engine, node.local(x), n_segments=4, depth=2)[None]

seg_ar = ctx.spmd(seg_demo, x, out_specs=P("node"))
assert np.array_equal(np.asarray(seg_ar), np.asarray(ar))  # bit-identical
print("segmented ring all-reduce == monolithic (pipelined wire): OK")

print("\nSwap backend='gascore' (or a mixed map like 'xla,gascore') in the")
print("Context to run the same program on the Pallas remote-DMA engine")
print("(see examples/heterogeneous_pipeline.py).")
