"""The paper's signature demo (§II/§IV): software and hardware nodes
cooperating transparently through one API.

Act 1 — *develop in software*: a producer/consumer pipeline where every
stage communicates with one-sided puts through the XLA ("software GASNet")
engine.  Act 2 — *migrate to hardware*: the identical program runs with the
GAScore engine (Pallas remote-DMA kernels, TPU-interpret on CPU), and the
results match bit-for-bit semantics.  Act 3 — a serving-shaped use: a
"prefill node" hands a KV cache to a "decode node" with a single one-sided
put (disaggregated inference transfer).

Run:  PYTHONPATH=src python examples/heterogeneous_pipeline.py
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import gasnet
from repro.launch.mesh import make_mesh

N = 4
mesh = make_mesh((N,), ("node",))


# A 3-stage pipeline over the ring: each node transforms what the previous
# node put into its inbox, then puts the result onward.
def pipeline_program(node, inbox, x):
    # stage 0: produce
    work = node.local(x)
    for _hop in range(N - 1):
        # transform then one-sided put to the right neighbor's inbox
        work = jnp.tanh(work) * 1.1
        inbox = node.put(inbox, work, to=gasnet.Shift(1), index=0)
        node.barrier()
        work = node.local(inbox).reshape(-1)[: work.shape[0]]
    return work[None]


def run(backend: str) -> np.ndarray:
    ctx = gasnet.Context(mesh, node_axis="node", backend=backend)
    aspace = ctx.address_space()
    aspace.register("inbox", (128,), jnp.float32)
    inbox = aspace.alloc("inbox")
    x = jnp.tile(jnp.linspace(-1, 1, 128)[None], (N, 1)).astype(jnp.float32)
    out = ctx.spmd(
        functools.partial(pipeline_program),
        inbox, x, out_specs=P("node"),
    )
    return np.asarray(out)


print("Act 1: run the pipeline on the SOFTWARE engine (XLA collectives)")
sw = run("xla")
print("  node 0 out[:4] =", sw[0, :4])

print("Act 2: migrate to the HARDWARE engine (GAScore Pallas remote-DMA)")
hw = run("gascore")
print("  node 0 out[:4] =", hw[0, :4])
np.testing.assert_allclose(sw, hw, rtol=1e-6)
print("  identical results — zero application changes.")

# --------------------------------------------------------------------------- #
print("Act 2b: MIX them — a heterogeneous node map, software and hardware")
print("nodes cooperating in ONE job (the paper's cluster, §II)")

from repro.launch.mesh import node_backends

backends = node_backends(N, pattern="alternating")  # sw, hw, sw, hw
print("  node map:", dict(enumerate(backends)))
mixed = run(",".join(backends))
np.testing.assert_allclose(sw, mixed, rtol=1e-6)
print("  identical results again — each rank on its own engine, one API.")

# --------------------------------------------------------------------------- #
print("Act 3: disaggregated serving — prefill node puts a KV cache into the")
print("decode node's memory with ONE one-sided GAScore transfer")

from repro.kernels import gascore
from jax import shard_map

S, KH, Dh = 32, 2, 16
kv = jnp.asarray(
    np.random.default_rng(0).normal(size=(N, S * KH * Dh)), jnp.float32
)
empty = jnp.zeros((N, 2 * S * KH * Dh), jnp.float32)


def handoff(seg, kv_l):
    # prefill node (every node plays both roles on the ring) writes its
    # computed KV block at offset S*KH*Dh of the decode node's cache segment
    return gascore.offset_put(
        seg[0], kv_l[0], jnp.int32(S * KH * Dh), k=1, axis="node", n_nodes=N
    )[None]


seg = jax.jit(
    shard_map(handoff, mesh=mesh, in_specs=(P("node"), P("node")),
                  out_specs=P("node"), check_vma=False)
)(empty, kv)
got = np.asarray(seg)
for d in range(N):
    np.testing.assert_allclose(
        got[d, S * KH * Dh :], np.asarray(kv)[(d - 1) % N]
    )
print("  KV cache landed at the receiver-side offset chosen by the sender —")
print("  the GAScore command format (local addr, node, remote addr, len).")
