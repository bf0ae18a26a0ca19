import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST be the first two lines: jax locks the device count on first init.
"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this builds the full-scale model ABSTRACTLY (eval_shape — no
parameter allocation), constructs the jit'd step with explicit in/out
shardings, then::

    lowered  = jax.jit(step, in_shardings=..., ...).lower(*structs)
    compiled = lowered.compile()
    compiled.memory_analysis()   # proves the per-device working set
    compiled.cost_analysis()     # FLOPs / bytes for the roofline
    parse(compiled.as_text())    # per-type collective operand bytes

and writes one JSON record per cell (results/dryrun/<cell>.json) that
EXPERIMENTS.md §Dry-run and §Roofline read.

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-405b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import dataclasses
import json
import re
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.registry import ARCHS, SHAPES, cell_runnable
from repro.launch.mesh import make_production_mesh, mesh_axes
from repro.models.build import ShapeConfig, build_model
from repro.optim import adamw
from repro.parallel.ctx import RunCtx

COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(m: re.Match) -> int:
    dt, dims = m.group(1), m.group(2)
    if dt not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def parse_collective_bytes(hlo_text: str) -> Dict[str, Any]:
    """Sum operand bytes of every collective op in (post-SPMD) HLO."""
    per_type: Dict[str, int] = {c: 0 for c in COLLECTIVES}
    counts: Dict[str, int] = {c: 0 for c in COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        if "fusion" in s.split("=")[0]:
            continue
        for coll in COLLECTIVES:
            # match "= TYPE[...] coll(" and "coll-start(" variants
            if re.search(rf"\b{coll}(-start)?\(", s):
                # operand types appear inline in the argument list
                try:
                    args = s.split(f"{coll}", 1)[1]
                    args = args.split("(", 1)[1]
                except IndexError:
                    continue
                depth = 1
                arg_str = []
                for ch in args:
                    if ch == "(":
                        depth += 1
                    elif ch == ")":
                        depth -= 1
                        if depth == 0:
                            break
                    arg_str.append(ch)
                arg_str = "".join(arg_str)
                b = sum(_shape_bytes(m) for m in _SHAPE_RE.finditer(arg_str))
                per_type[coll] += b
                counts[coll] += 1
                break
    total = sum(per_type.values())
    return {"per_type": per_type, "counts": counts, "total": total}


# --------------------------------------------------------------------------- #
def build_ctx(mesh, *, attn_chunk: int = 512, remat: str = "full",
              moe_mode: str = "auto", fsdp_gather: bool = False,
              seq_shard_acts: bool = False, scan_impl: str = "ref") -> RunCtx:
    dp, tp = mesh_axes(mesh)
    return RunCtx(
        mesh=mesh, dp=dp, tp=tp, remat=remat, moe_mode=moe_mode,
        attn_impl="chunked", attn_chunk=attn_chunk, scan_impl=scan_impl,
        fsdp_gather=fsdp_gather,
        seq_shard_acts=seq_shard_acts,
    )


def make_step(model, ctx: RunCtx, shape: ShapeConfig, opt_cfg=None):
    """Returns (fn, arg_structs, in_shardings) for the cell's step kind."""
    from repro.parallel.sharding import named_shardings

    mesh = ctx.mesh
    opt_cfg = opt_cfg or adamw.AdamWConfig(
        schedule=adamw.warmup_cosine(3e-4, 2000, 100000),
        state_dtype=jnp.float32 if model.cfg.d_model < 8192 else jnp.bfloat16,
    )

    params_struct, specs = model.abstract_init(ctx)
    p_shard = named_shardings(specs, params_struct, mesh)

    batch_structs = model.input_structs(shape)
    batch_shard = named_shardings(
        model.input_specs(shape, ctx), batch_structs, mesh
    )

    if shape.kind == "train":
        opt_struct = jax.eval_shape(
            lambda p: adamw.init_state(p, opt_cfg), params_struct
        )
        o_shard = named_shardings(
            adamw.state_specs(specs), opt_struct, mesh
        )

        def train_step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(
                lambda p: model.train_loss(p, ctx, batch)
            )(params)
            params, opt_state, metrics = adamw.apply_updates(
                params, grads, opt_state, opt_cfg
            )
            metrics["loss"] = loss
            return params, opt_state, metrics

        fn = jax.jit(
            train_step,
            in_shardings=(p_shard, o_shard, batch_shard),
            out_shardings=(p_shard, o_shard, None),
            donate_argnums=(0, 1),
        )
        return fn, (params_struct, opt_struct, batch_structs)

    if shape.kind == "prefill":
        def prefill_step(params, batch):
            return model.prefill(params, ctx, batch, cache_len=shape.seq_len)

        fn = jax.jit(
            prefill_step, in_shardings=(p_shard, batch_shard)
        )
        return fn, (params_struct, batch_structs)

    # decode
    cache_struct = model.cache_structs(shape, ctx)
    cache_shard = named_shardings(
        model.cache_specs(cache_struct, ctx), cache_struct, mesh
    )

    def serve_step(params, token, positions, caches):
        return model.decode_step(params, ctx, token, positions, caches)

    fn = jax.jit(
        serve_step,
        in_shardings=(
            p_shard,
            batch_shard["token"],
            batch_shard["positions"],
            cache_shard,
        ),
        donate_argnums=(3,),
    )
    structs = (
        params_struct,
        batch_structs["token"],
        batch_structs["positions"],
        cache_struct,
    )
    return fn, structs


def run_cell(
    arch: str,
    shape_name: str,
    mesh_kind: str,
    *,
    out_dir: str = "results/dryrun",
    overrides: Optional[Dict[str, Any]] = None,
    tag: str = "baseline",
) -> Dict[str, Any]:
    cfg = ARCHS[arch]
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    overrides = overrides or {}
    ctx = build_ctx(mesh, **overrides.get("ctx", {}))
    model = build_model(
        dataclasses.replace(cfg, **overrides.get("cfg", {}))
        if overrides.get("cfg")
        else cfg
    )
    rec: Dict[str, Any] = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_kind,
        "tag": tag,
        "n_devices": mesh.devices.size,
        "status": "ok",
    }
    t0 = time.time()
    try:
        fn, structs = make_step(model, ctx, shape)
        lowered = fn.lower(*structs)
        rec["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 1)
        try:
            ma = compiled.memory_analysis()
            rec["memory"] = {
                k: int(getattr(ma, k))
                for k in (
                    "argument_size_in_bytes",
                    "output_size_in_bytes",
                    "temp_size_in_bytes",
                    "alias_size_in_bytes",
                    "generated_code_size_in_bytes",
                )
                if hasattr(ma, k)
            }
        except Exception as e:  # pragma: no cover
            rec["memory"] = {"error": str(e)}
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0]
            rec["cost_xla"] = {
                "flops": float(ca.get("flops", -1)),
                "bytes_accessed": float(ca.get("bytes accessed", -1)),
            }
        except Exception as e:  # pragma: no cover
            rec["cost_xla"] = {"error": str(e)}
        text = compiled.as_text()
        # trip-count-aware analysis (XLA's cost_analysis counts while
        # bodies once; see launch/hlostats.py)
        from repro.launch import hlostats

        st = hlostats.analyze(text)
        rec["cost"] = {"flops": st.flops, "bytes_accessed": st.bytes}
        rec["collectives"] = {
            "per_type": st.collective_per_type,
            "counts": st.collective_counts,
            "total": st.collective_bytes,
        }
        rec["while_trips"] = {
            k: v for k, v in sorted(st.while_trips.items())[:40]
        }
        rec["unresolved_whiles"] = st.unresolved_whiles
        rec["hlo_chars"] = len(text)
    except Exception as e:
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    finally:
        jax.clear_caches()  # bound sweep memory: drop executables between cells
    rec["total_s"] = round(time.time() - t0, 1)

    os.makedirs(out_dir, exist_ok=True)
    fname = f"{arch}__{shape_name}__{mesh_kind}__{tag}.json"
    with open(os.path.join(out_dir, fname), "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--remat", default="full")
    ap.add_argument("--attn-chunk", type=int, default=512)
    ap.add_argument("--moe-mode", default="auto")
    ap.add_argument("--fsdp-gather", action="store_true")
    ap.add_argument("--seq-shard-acts", action="store_true")
    ap.add_argument("--scan-impl", default="ref")
    args = ap.parse_args()

    cells = []
    archs = list(ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    for a in archs:
        for s in shapes:
            ok, why = cell_runnable(a, s)
            if not ok:
                print(f"SKIP {a} × {s}: {why}")
                continue
            for m in meshes:
                cells.append((a, s, m))

    overrides = {
        "ctx": {
            "remat": args.remat,
            "attn_chunk": args.attn_chunk,
            "moe_mode": args.moe_mode,
            "fsdp_gather": args.fsdp_gather,
            "seq_shard_acts": args.seq_shard_acts,
            "scan_impl": args.scan_impl,
        }
    }
    for a, s, m in cells:
        rec = run_cell(a, s, m, out_dir=args.out, overrides=overrides,
                       tag=args.tag)
        status = rec["status"]
        extra = (
            f"flops={rec.get('cost', {}).get('flops', 0):.3e} "
            f"coll={rec.get('collectives', {}).get('total', 0):.3e}B "
            f"lower={rec.get('lower_s')}s compile={rec.get('compile_s')}s"
            if status == "ok"
            else rec.get("error", "")
        )
        print(f"[{status}] {a} × {s} × {m}: {extra}", flush=True)


if __name__ == "__main__":
    main()
