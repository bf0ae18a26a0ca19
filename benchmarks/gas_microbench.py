"""GASNet-style microbenchmarks (the paper's evaluation lineage, cf. [4]):
AM round-trip latency, one-sided put bandwidth, collective comparison,
blocking vs split-phase (Extended API) comm/compute overlap, and the
scheduler's segmented-vs-monolithic ring pipeline.

Needs 8 devices; on the CPU run it with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.  Prints
``name,us_per_call,derived`` CSV rows; with ``--json PATH`` (default
``BENCH_gas.json`` when the flag is given bare) it also writes a
machine-readable artifact: every row, per-op bytes/sec, the overlap gap,
the segmented-vs-monolithic speedups per payload tier, and the measured
per-engine cost constants (``engine_costs``) that ``repro.core.sched``
can load back as its planning model.
"""
import argparse
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.launch.mesh import make_mesh

from _report import make_report, new_result, write_artifact

RESULT = new_result()
report = make_report(RESULT)


def timeit(fn, *args, iters=20, warmup=3):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6  # us


def interleaved_us(fns, iters=9, warmup=3):
    """Interleaved A/B/... medians: host-device timings drift, and a
    sequential comparison aliases that drift into the gap."""
    for f in fns:
        for _ in range(warmup):
            jax.block_until_ready(f())
    t = [[] for _ in fns]
    for _ in range(iters):
        for i, f in enumerate(fns):
            t0 = time.perf_counter()
            jax.block_until_ready(f())
            t[i].append(time.perf_counter() - t0)
    return [float(np.median(ti)) * 1e6 for ti in t]


def main(json_path: str | None = None) -> None:
    from repro.core import gasnet
    from repro.core.engine import make_engine
    from repro.core import collectives
    from repro.optim import compression

    N = 8
    mesh = make_mesh((N,), ("node",))

    # ---- AM round trip latency vs payload -------------------------------- #
    for width in (8, 64, 512):
        ctx = gasnet.Context(mesh, node_axis="node", backend="xla",
                             am_payload_width=width, am_capacity=2)
        ctx.handlers.register(
            "store",
            lambda state, payload, args: {"buf": payload.astype(jnp.float32)},
        )

        def am_rt(seg):
            def prog(node, seg):
                state = {"buf": jnp.zeros((width,), jnp.float32)}
                node.am_medium(
                    jnp.asarray((node.my_id + 1) % N, jnp.int32), "store",
                    payload=node.local(seg)[:width],
                )
                state = node.am_flush(state)
                # reply leg: send it back
                node.am_medium(
                    jnp.asarray((node.my_id - 1) % N, jnp.int32), "store",
                    payload=state["buf"],
                )
                state = node.am_flush(state)
                return state["buf"][None]

            return ctx.spmd(prog, seg, out_specs=P("node"))

        aspace = ctx.address_space()
        aspace.register("b", (max(width, 8),), jnp.float32)
        seg = aspace.alloc("b", init_fn=jnp.ones)
        us = timeit(am_rt, seg)
        report(f"am_roundtrip_w{width}", us, f"payload={width * 4}B",
               op="am_roundtrip", payload_bytes=width * 4)

    # ---- one-sided put bandwidth vs size ---------------------------------- #
    ctx = gasnet.Context(mesh, node_axis="node", backend="xla")
    for size in (1 << 10, 1 << 14, 1 << 18, 1 << 20):
        n_el = size // 4
        aspace = ctx.address_space()
        name = f"bw{size}"
        aspace.register(name, (n_el,), jnp.float32)
        seg = aspace.alloc(name)

        def put_prog(node, seg):
            data = jnp.ones((n_el,), jnp.float32) * node.my_id
            return node.put(seg, data, to=gasnet.Shift(1), index=0)

        us = timeit(lambda s: ctx.spmd(put_prog, s), seg)
        gbps = size / (us * 1e-6) / 1e9
        report(f"put_{size}B", us, f"{gbps:.3f}GB/s/node",
               op="put", payload_bytes=size,
               bytes_per_sec=round(size / (us * 1e-6), 1))

    # ---- collectives: GAS ring (xla engine) vs lax natives ---------------- #
    M = 1 << 16  # 64k f32 per node contribution
    x = jnp.ones((N, M), jnp.float32)

    def ring_ar(xl):
        eng = make_engine("xla", "node", N)
        return collectives.ring_all_reduce(eng, xl[0])[None]

    def native_ar(xl):
        return jax.lax.psum(xl[0], "node")[None]

    for nm, fn in (("ring_allreduce", ring_ar), ("xla_allreduce", native_ar)):
        f = jax.jit(shard_map(fn, mesh=mesh, in_specs=(P("node"),),
                                  out_specs=P("node"), check_vma=False))
        us = timeit(f, x)
        report(f"{nm}_{M * 4}B", us,
               f"sum_ok={bool(jnp.allclose(f(x)[0], N))}",
               op=nm, payload_bytes=M * 4,
               bytes_per_sec=round(M * 4 / (us * 1e-6), 1))

    # ---- int8 EF compressed ring vs f32 ring ------------------------------ #
    def comp_ar(xl):
        eng = make_engine("xla", "node", N)
        red, _ = compression.compressed_ring_all_reduce(
            eng, xl[0], jnp.zeros((M,), jnp.float32)
        )
        return red[None]

    f = jax.jit(shard_map(comp_ar, mesh=mesh, in_specs=(P("node"),),
                              out_specs=P("node"), check_vma=False))
    us = timeit(f, x)
    wire_f32 = 2 * (N - 1) / N * M * 4
    wire_int8 = 2 * (N - 1) / N * (M * 1 + 4)
    report(f"compressed_ring_{M * 4}B", us,
           f"wire_bytes {wire_int8 / wire_f32:.2f}x_of_f32",
           op="compressed_ring", payload_bytes=M * 4)

    # ---- blocking vs split-phase: comm/compute overlap (Extended API) ----- #
    # Ring pipeline, one heavy transform per received chunk (the transform
    # feeds only the final accumulator, not the forwarded packet).
    #
    #   blocking:    each hop's transfer must COMPLETE before the next
    #                statement runs (gasnet_put semantics) — modeled with an
    #                explicit ordering fence, so per hop: T + C.
    #   split-phase: hop h+1's put is initiated before hop h's transform
    #                (put_nb ... sync), so the transform may overlap the
    #                wire — per hop: max(T, C).
    #
    # Two numbers are reported:
    #   overlap_gain_bound    — (T+C)/max(T,C) from individually measured
    #                           per-hop transfer (T) and transform (C)
    #                           costs: the gap a node with a dedicated
    #                           communication engine (the paper's GAScore /
    #                           TPU ICI DMA) realizes, since the transfer
    #                           burns no compute-core cycles there.
    #   overlap_gain_measured — interleaved-median wall clock of the two
    #                           schedules on THIS host.  CPU host devices
    #                           execute transfers with the same cores that
    #                           run the transform, so on an oversubscribed
    #                           machine this tends toward 1.0 — which is
    #                           precisely the software-node bottleneck the
    #                           paper builds hardware nodes to remove.
    from jax import lax

    B, D = 8192, 128  # 4 MiB chunk per hop; transform = chunk @ (D, D)
    w_ov = jnp.eye(D, dtype=jnp.float32) * 0.5

    def transform(c, w):
        return jnp.tanh(c @ w)

    def blocking_ring(xl, w):
        eng = make_engine("xla", "node", N)
        cur = xl
        acc = jnp.zeros_like(cur)
        for _ in range(1, N):
            cur = eng.shift(cur, 1)          # blocking put: completes here
            acc = acc + transform(cur, w)
            # a blocking runtime cannot initiate hop h+1 until hop h's
            # statement finished — make that ordering edge explicit
            cur, acc = lax.optimization_barrier((cur, acc))
        return acc

    def overlap_ring(xl, w):
        eng = make_engine("xla", "node", N)
        cur = xl
        acc = jnp.zeros_like(cur)
        pending = eng.shift_nb(cur, 1)       # initiate hop 1
        for h in range(1, N):
            cur = pending.wait()             # sync hop h
            if h < N - 1:
                pending = eng.shift_nb(cur, 1)  # initiate hop h+1 first...
            acc = acc + transform(cur, w)       # ...then compute (overlapped)
        return acc

    def wrap(fn):
        def g(xl, w):
            return fn(xl[0], w)[None]
        return jax.jit(shard_map(g, mesh=mesh, in_specs=(P("node"), P()),
                                 out_specs=P("node"), check_vma=False))

    xs = jnp.ones((N, B, D), jnp.float32) * 0.01
    f_blk, f_ovl = wrap(blocking_ring), wrap(overlap_ring)
    assert bool(jnp.allclose(f_blk(xs, w_ov), f_ovl(xs, w_ov), rtol=1e-5))

    # per-hop costs measured in isolation (stable even on loaded hosts)
    def one_hop(xl, w):
        eng = make_engine("xla", "node", N)
        return eng.shift(xl[0], 1)[None]

    def one_transform(xl, w):
        return transform(xl[0], w)[None]

    f_T = jax.jit(shard_map(one_hop, mesh=mesh, in_specs=(P("node"), P()),
                            out_specs=P("node"), check_vma=False))
    f_C = jax.jit(shard_map(one_transform, mesh=mesh,
                            in_specs=(P("node"), P()),
                            out_specs=P("node"), check_vma=False))
    us_T = timeit(f_T, xs, w_ov, iters=10)
    us_C = timeit(f_C, xs, w_ov, iters=10)
    bound = (us_T + us_C) / max(us_T, us_C)
    report(f"hop_transfer_{B * D * 4}B", us_T, "T")
    report(f"hop_transform_{B * D * 4}B", us_C, "C")
    report("overlap_gain_bound", bound, "x=(T+C)/max(T:C)_hw_comm_engine",
           unit="x")

    us_blk, us_ovl = interleaved_us(
        (lambda: f_blk(xs, w_ov), lambda: f_ovl(xs, w_ov))
    )
    report(f"blocking_ring_{B * D * 4}B", us_blk, "per_hop=T+C")
    report(f"splitphase_ring_{B * D * 4}B", us_ovl, "per_hop=max(T:C)")
    report("overlap_gain_measured", us_blk / us_ovl, "x_on_shared_cpu_cores",
           unit="x")
    RESULT["overlap"] = {
        "gain_bound": round(bound, 3),
        "gain_measured": round(us_blk / us_ovl, 3),
    }

    # ---- scheduler: segmented vs monolithic ring all-reduce per tier ------ #
    # The scheduler chunks each ring payload into n_segments slices with
    # ``depth`` puts in flight, so segment k+1's wire overlaps segment k's
    # accumulate epilogue (the GAScore command-FIFO drain).  Two numbers
    # per payload tier, mirroring the overlap section above:
    #
    #   speedup_comm_engine — per-hop pipeline model from individually
    #       measured wire (T) and epilogue (C) costs:
    #       (T+C) / (max(T,C) + min(T,C)/G) — what a node with a dedicated
    #       communication engine realizes, since its DMAs burn no compute
    #       cycles.  > 1 whenever the plan segments (G > 1).
    #   speedup_measured    — interleaved-median wall clock on THIS host,
    #       where "wire" is the same CPU cores as the epilogue — the
    #       software-node bottleneck the paper builds hardware nodes to
    #       remove (tends toward 1.0 on oversubscribed machines).
    from repro.core import collectives, sched

    RESULT["segmented_allreduce"] = {}
    for Mseg in (1 << 18, 1 << 20, 1 << 22):  # 1/4/16 MiB f32 per node
        xseg = jnp.ones((N, Mseg), jnp.float32)
        plan = sched.plan_collective(
            "all_reduce", nbytes=Mseg * 4, n_nodes=N,
            engine=make_engine("xla", "node", N),
        )
        G, D_ = plan.n_segments, plan.depth
        if G == 1:
            G, D_ = 4, 2  # pin segmentation on tiers the model leaves whole

        def mono_ar(xl):
            eng = make_engine("xla", "node", N)
            return collectives.ring_all_reduce(eng, xl[0])[None]

        def seg_ar(xl, G=G, D_=D_):
            eng = make_engine("xla", "node", N)
            return collectives.segmented_ring_all_reduce(
                eng, xl[0], n_segments=G, depth=D_
            )[None]

        f_mono = jax.jit(shard_map(mono_ar, mesh=mesh, in_specs=(P("node"),),
                                   out_specs=P("node"), check_vma=False))
        f_seg = jax.jit(shard_map(seg_ar, mesh=mesh, in_specs=(P("node"),),
                                  out_specs=P("node"), check_vma=False))
        assert bool(jnp.allclose(f_mono(xseg), f_seg(xseg)))

        # per-hop wire (T) and accumulate-epilogue (C) costs, in isolation
        chunk = Mseg // N

        def hop_wire(xl):
            eng = make_engine("xla", "node", N)
            return eng.shift(xl[0, :chunk], 1)[None]

        def hop_epilogue(xl):
            return (xl[0, :chunk] + xl[0, chunk : 2 * chunk])[None]

        f_T2 = jax.jit(shard_map(hop_wire, mesh=mesh, in_specs=(P("node"),),
                                 out_specs=P("node"), check_vma=False))
        f_C2 = jax.jit(shard_map(hop_epilogue, mesh=mesh,
                                 in_specs=(P("node"),),
                                 out_specs=P("node"), check_vma=False))
        t_wire = timeit(f_T2, xseg, iters=8)
        t_epi = timeit(f_C2, xseg, iters=8)
        pipe = max(t_wire, t_epi) + min(t_wire, t_epi) / G
        speedup_engine = (t_wire + t_epi) / pipe
        us_mono, us_seg = interleaved_us(
            (lambda: f_mono(xseg), lambda: f_seg(xseg)), iters=7
        )
        measured = us_mono / us_seg
        nb = Mseg * 4
        report(f"monolithic_allreduce_{nb}B", us_mono,
               f"ring_{2 * (N - 1)}hops", op="allreduce_monolithic",
               payload_bytes=nb, bytes_per_sec=round(nb / (us_mono * 1e-6), 1))
        report(f"segmented_allreduce_{nb}B", us_seg,
               f"speedup_vs_monolithic={speedup_engine:.3f}x_with_comm_engine"
               f"(measured={measured:.3f}x_shared_cores)_plan={G}x{D_}",
               op="allreduce_segmented", payload_bytes=nb,
               bytes_per_sec=round(nb / (us_seg * 1e-6), 1))
        RESULT["segmented_allreduce"][str(nb)] = {
            "monolithic_us": round(us_mono, 1),
            "segmented_us": round(us_seg, 1),
            "n_segments": G,
            "depth": D_,
            "hop_wire_us": round(t_wire, 1),
            "hop_epilogue_us": round(t_epi, 1),
            "speedup_comm_engine": round(speedup_engine, 3),
            "speedup_measured": round(measured, 3),
        }

    # ---- measured engine cost constants (the scheduler's planning model) -- #
    # Per engine: alpha from a tiny hop, beta from the large-hop slope;
    # gamma (the local accumulate epilogue) is engine-independent.
    # repro.core.sched.load_costs() reads these back, including for the
    # worst-member planning of heterogeneous EngineMaps.
    kib = (chunk * 4) / 1024.0
    gamma = max(0.0, t_epi / kib)
    RESULT["engine_costs"] = {}
    # gascore hops run in Pallas TPU-interpret mode: keep shapes modest
    # ((rows, 128) trailing-dim-128 f32) so the measurement stays cheap
    probe = {
        "xla": (256, 8192),     # rows of 128 f32: 128 KiB / 4 MiB hops
        "gascore": (2, 256),    # 1 KiB / 128 KiB hops
    }
    xprobe = jnp.ones((N, 8192, 128), jnp.float32)
    for backend, (rows_a, rows_b) in probe.items():
        def hop(xl, backend=backend, rows=None):
            eng = make_engine(backend, "node", N)
            return eng.shift(xl[0, :rows], 1)[None]

        def make_f(rows):
            return jax.jit(shard_map(
                functools.partial(hop, rows=rows), mesh=mesh,
                in_specs=(P("node"),), out_specs=P("node"), check_vma=False,
            ))

        iters = 10 if backend == "xla" else 3
        alpha = timeit(make_f(rows_a), xprobe, iters=iters)
        t_big = timeit(make_f(rows_b), xprobe, iters=iters)
        big_kib = rows_b * 128 * 4 / 1024.0
        beta = max(0.0, (t_big - alpha) / big_kib)
        RESULT["engine_costs"][backend] = {
            "alpha_us": round(alpha, 2),
            "beta_us_per_kib": round(beta, 4),
            "gamma_us_per_kib": round(gamma, 4),
        }
        report(f"engine_cost_alpha_{backend}", alpha, "us_per_hop")
        report(f"engine_cost_beta_{backend}", beta, "us_per_kib_wire",
               unit="us_per_kib")
    report("engine_cost_gamma", gamma, "us_per_kib_epilogue",
           unit="us_per_kib")

    if json_path:
        write_artifact(RESULT, json_path)

    print("GAS_BENCH_DONE")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--json", nargs="?", const="BENCH_gas.json", default=None,
        metavar="PATH",
        help="write the machine-readable artifact (default: BENCH_gas.json)",
    )
    from repro.launch import compile_cache

    compile_cache.enable()
    main(json_path=ap.parse_args().json)
