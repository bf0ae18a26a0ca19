"""PGAS scientific kernel: 1-D Jacobi heat diffusion with halo exchange.

The paper closes by planning "higher-level communication abstractions
... for parallel scientific computations"; the canonical PGAS citizen is a
stencil whose halo exchange is a pair of one-sided puts per step.  Each
node owns an interior strip of the rod plus two ghost cells; every
iteration puts its boundary values into the neighbors' ghost cells through
the GAS API and relaxes locally.  Verified against the single-device dense
reference.

Run:  PYTHONPATH=src python examples/stencil_halo.py
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import gasnet
from repro.launch.mesh import make_mesh

N_NODES = 8
LOCAL = 64  # interior cells per node
STEPS = 400
ALPHA = 0.25

mesh = make_mesh((N_NODES,), ("node",))
ctx = gasnet.Context(mesh, node_axis="node", backend="xla")

# segment layout per node: [ghost_left | interior(LOCAL) | ghost_right]
aspace = ctx.address_space()
aspace.register("rod", (LOCAL + 2,), jnp.float32)

# initial condition: a hot spike in node 0's strip, fixed 0 boundaries
init = np.zeros((N_NODES, LOCAL + 2), np.float32)
init[0, 1 + LOCAL // 2] = 100.0
seg = aspace.alloc_from("rod", jnp.asarray(init))


def jacobi(node, seg):
    def step(seg, _):
        u = node.local(seg)
        # halo exchange: one-sided puts of boundary cells into neighbors
        seg = node.put(seg, u[1:2], to=gasnet.Shift(-1), index=LOCAL + 1)
        seg = node.put(seg, u[LOCAL : LOCAL + 1], to=gasnet.Shift(1), index=0)
        u = node.local(seg)
        # physical boundary: the rod ends see zero ghosts (the ring wraps,
        # so the end nodes must overwrite the wrapped-around halo)
        is_first = node.my_id == 0
        is_last = node.my_id == node.n_nodes - 1
        u = u.at[0].set(jnp.where(is_first, 0.0, u[0]))
        u = u.at[-1].set(jnp.where(is_last, 0.0, u[-1]))
        interior = u[1:-1]
        new = interior + ALPHA * (u[:-2] - 2 * interior + u[2:])
        seg = gasnet.Node._restore(seg, u.at[1:-1].set(new))
        return seg, new.sum()

    seg, heat = jax.lax.scan(step, seg, None, length=STEPS)
    return seg, heat[None]


seg_out, heat = ctx.spmd(jacobi, seg, out_specs=(P("node"), P("node")))

# ---- dense single-device reference ---------------------------------------- #
rod = np.zeros(N_NODES * LOCAL, np.float32)
rod[LOCAL // 2] = 100.0
for _ in range(STEPS):
    padded = np.pad(rod, 1)  # zero ends
    rod = rod + ALPHA * (padded[:-2] - 2 * rod + padded[2:])

got = np.asarray(seg_out)[:, 1:-1].reshape(-1)
np.testing.assert_allclose(got, rod, atol=1e-4)
print(f"Jacobi rod after {STEPS} steps: max={got.max():.4f}, "
      f"total heat={got.sum():.4f}")
print("distributed PGAS result matches the dense reference — OK")
