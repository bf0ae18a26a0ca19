"""End-to-end driver: data-parallel LM training ON the GAS layer, with
int8 error-feedback gradient rings, async checkpoints, an injected node
failure, and an elastic restart on the surviving nodes.

This is the explicit-DP path of the framework: 8 host devices act as 8
GASNet nodes; every node computes grads on its microbatch and the gradient
reduction is the paper's communication substrate — a ring of one-sided
puts (``--reduce gas_ring``), optionally int8-compressed with error
feedback (``--reduce gas_ring_int8``), or XLA's fused ``psum`` for
reference (``--reduce psum``).  At --fail-at the process loses two nodes;
``elastic_plan`` proposes the 6-node mesh, the latest snapshot restores
onto it, and the deterministic data stream resumes where it left off.

Run:  PYTHONPATH=src python examples/train_lm.py --steps 200
      PYTHONPATH=src python examples/train_lm.py --reduce gas_ring_int8
"""
import argparse
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.checkpoint import ckpt
from repro.configs.registry import SMOKE
from repro.core import sched
from repro.launch.mesh import make_mesh
from repro.core.engine import make_engine
from repro.data.synthetic import SyntheticLM
from repro.models.build import build_model
from repro.optim import adamw, compression
from repro.parallel.ctx import RunCtx
from repro.runtime.ft import elastic_plan
from jax import shard_map


def make_step(model, opt_cfg, mesh, n_nodes, reduce_mode):
    """Explicit-DP train step: local grads -> GAS ring reduction -> AdamW."""
    local_ctx = RunCtx(mesh=None, remat="none")

    def node_program(params, opt_state, err, batch):
        loss, grads = jax.value_and_grad(
            lambda p: model.train_loss(p, local_ctx, batch)
        )(params)
        eng = make_engine("xla", "node", n_nodes)
        if reduce_mode == "psum":
            grads = jax.tree.map(lambda g: jax.lax.pmean(g, "node"), grads)
        elif reduce_mode == "gas_ring":
            leaves, treedef = jax.tree.flatten(grads)
            flat = jnp.concatenate(
                [x.reshape(-1).astype(jnp.float32) for x in leaves]
            )
            pad = (-flat.shape[0]) % n_nodes
            flat = jnp.pad(flat, (0, pad))
            # plan-driven: size-aware algorithm selection + segmentation
            # (ring for these payload sizes, recursive doubling for tiny)
            red = sched.all_reduce(eng, flat) / n_nodes
            out, off = [], 0
            for x in leaves:
                out.append(red[off : off + x.size].reshape(x.shape).astype(x.dtype))
                off += x.size
            grads = treedef.unflatten(out)
        elif reduce_mode == "gas_ring_int8":
            grads, err = compression.compressed_all_reduce_tree(eng, grads, err)
        loss = jax.lax.pmean(loss, "node")
        params, opt_state, metrics = adamw.apply_updates(
            params, grads, opt_state, opt_cfg
        )
        metrics["loss"] = loss
        return params, opt_state, err, metrics

    rep = P()  # replicated over nodes

    def batch_specs(b):
        return jax.tree.map(lambda _: P("node"), b)

    def step(params, opt_state, err, batch):
        return shard_map(
            node_program,
            mesh=mesh,
            in_specs=(rep, rep, rep, batch_specs(batch)),
            out_specs=(rep, rep, rep, rep),
            check_vma=False,
        )(params, opt_state, err, batch)

    return jax.jit(step, donate_argnums=(0, 1, 2))


def place_batch(batch, mesh):
    return {
        k: jax.device_put(
            v, NamedSharding(mesh, P("node", *([None] * (v.ndim - 1))))
        )
        for k, v in batch.items()
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--reduce", default="gas_ring_int8",
                    choices=["psum", "gas_ring", "gas_ring_int8"])
    ap.add_argument("--batch", type=int, default=24)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--fail-at", type=int, default=120)
    ap.add_argument("--ckpt-every", type=int, default=40)
    ap.add_argument("--ckpt-dir", default="/tmp/gaspax_train_lm")
    args = ap.parse_args()

    cfg = SMOKE["qwen3-4b"]
    model = build_model(cfg)
    opt_cfg = adamw.AdamWConfig(
        lr=3e-3, weight_decay=0.0,
        schedule=adamw.warmup_cosine(3e-3, 10, args.steps),
    )
    src = SyntheticLM(cfg, batch=args.batch, seq_len=args.seq, seed=1)
    os.makedirs(args.ckpt_dir, exist_ok=True)

    def build(n_nodes):
        mesh = make_mesh((n_nodes,), ("node",))
        step = make_step(model, opt_cfg, mesh, n_nodes, args.reduce)
        return mesh, step

    n_nodes = 8
    mesh, step_fn = build(n_nodes)
    params, _ = model.init(RunCtx(mesh=None), jax.random.PRNGKey(0))
    opt_state = adamw.init_state(params, opt_cfg)
    err = compression.init_error_state(params, n_nodes)

    print(f"training {cfg.name}-smoke on {n_nodes} GASNet nodes, "
          f"reduce={args.reduce}")
    t0 = time.time()
    data_step = 0
    handle = None
    step = 0
    while step < args.steps:
        try:
            batch = place_batch(src.batch_at(data_step), mesh)
            if step == args.fail_at and n_nodes == 8:
                raise RuntimeError("NODE FAILURE: nodes {6,7} lost")
            params, opt_state, err, m = step_fn(params, opt_state, err, batch)
            data_step += 1
            if step % 20 == 0 or step == args.steps - 1:
                print(f"  step {step:4d} loss {float(m['loss']):.4f} "
                      f"lr {float(m['lr']):.2e}")
            if (step + 1) % args.ckpt_every == 0:
                if handle:
                    handle.wait()
                handle = ckpt.save(
                    args.ckpt_dir, step + 1,
                    {"params": params, "opt": opt_state},
                    extra={"data_step": data_step},
                )
            step += 1
        except RuntimeError as e:
            print(f"  !! {e}")
            plan = elastic_plan(6, 1)
            n_nodes = plan[0] * plan[1] * plan[2]
            # keep global batch divisible by the new node count
            n_nodes = 6
            print(f"  elastic plan -> continue on {n_nodes} nodes")
            if handle:
                handle.wait()
            last = ckpt.latest_step(args.ckpt_dir)
            mesh, step_fn = build(n_nodes)
            tree, extra = ckpt.restore(
                args.ckpt_dir, last, {"params": params, "opt": opt_state}
            )
            params, opt_state = tree["params"], tree["opt"]
            err = compression.init_error_state(params, n_nodes)
            step = last
            data_step = int(extra["data_step"])
            print(f"  restored step {last} (data cursor {data_step}) — "
                  f"resuming")
    if handle:
        handle.wait()
    print(f"done in {time.time() - t0:.1f}s — final loss "
          f"{float(m['loss']):.4f} (started ~{np.log(cfg.vocab):.2f})")


if __name__ == "__main__":
    main()
