"""GAScore Pallas RDMA kernel suite + software/hardware engine parity
(4 devices, TPU interpret mode)."""
import os

if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.launch.mesh import make_mesh


BACKENDS = ("xla", "gascore", "xla,gascore")


def engine_parity(n: int = 4) -> dict:
    """The GAS layer's main operations on ``n`` devices for every engine
    (``xla``, ``gascore`` and the mixed map), required to agree bit for
    bit.  Values are small integers in float32, so any summation order
    gives the exact sum: a difference can only be a transport fault.

    Covers put, get, vectored get, vectored put, the AM request/reply
    round trip, the planned all-reduce and each engine's own all-reduce.
    Returns ``{op: result}`` of the ``xla`` engine."""
    from repro.core import am, gasnet, sched

    mesh = make_mesh((n,), ("node",))
    seg0 = (jnp.arange(n * 256, dtype=jnp.float32) % 97).reshape(n, 256)
    xs = (jnp.arange(n * n * 8 * 128, dtype=jnp.float32) % 13).reshape(
        n, n * 8, 128
    )

    def run(backend):
        ctx = gasnet.Context(mesh, node_axis="node", backend=backend,
                             am_payload_width=4)

        def pong(state, payload, args):
            out = dict(state)
            out["ack_payload"] = payload
            out["ack_arg"] = state["ack_arg"] + args[0]
            return out

        pong_id = ctx.handlers.register("pong", pong)

        def ping(state, payload, args):
            out = dict(state)
            out["got"] = state["got"] + args[0]
            return out, am.reply_medium(
                pong_id, payload * 2.0, args=(args[0] + 1,)
            )

        ctx.handlers.register("ping", ping, replies=True)

        def prog(node, seg, x):
            me = node.my_id
            out = {}
            out["put"] = node.put(
                seg, jnp.full((128,), 1.0 + me, jnp.float32),
                to=gasnet.Shift(1), index=64,
            )
            out["get"] = node.get(
                seg, frm=gasnet.Shift(n - 1), index=128, size=128
            )[None]
            out["get_v"] = node.get_v(
                seg, frm=gasnet.Shift(1), indices=[0, 128], size=128
            )[None]
            datas = jnp.stack(
                [jnp.full((128,), 3.0 + me + j, jnp.float32) for j in range(2)]
            )
            out["put_v"] = node.put_v(
                seg, datas, to=gasnet.Shift(2), indices=[0, 128],
                pred=[True, (me % 2) == 0],
            )
            state = {
                "got": jnp.zeros((), jnp.int32),
                "ack_arg": jnp.zeros((), jnp.int32),
                "ack_payload": jnp.zeros((4,), jnp.float32),
            }
            h = node.am_call(
                (me + 1) % n, "ping",
                payload=jnp.full((4,), 1.0 + me, jnp.float32),
                args=(me * 5,), ack=lambda st: st["ack_payload"],
            )
            state = node.am_flush(state)
            out["am"] = jnp.concatenate([
                state["got"][None].astype(jnp.float32),
                state["ack_arg"][None].astype(jnp.float32),
                node.sync(h),
            ])[None]
            out["all_reduce_planned"] = sched.all_reduce(
                node.engine, x[0]
            )[None]
            out["all_reduce"] = node.engine.all_reduce(x[0])[None]
            return out

        res = ctx.spmd(
            prog, seg0, xs,
            out_specs={k: P("node") for k in (
                "put", "get", "get_v", "put_v", "am",
                "all_reduce_planned", "all_reduce",
            )},
        )
        return {k: np.asarray(v) for k, v in res.items()}

    outs = {b: run(b) for b in BACKENDS}
    want_ar = np.tile(np.asarray(xs).sum(0), (n, 1, 1))
    for op in ("all_reduce_planned", "all_reduce"):
        np.testing.assert_array_equal(outs["xla"][op], want_ar, err_msg=op)
    for node in range(n):
        am_row = outs["xla"]["am"][node]
        assert am_row[0] == ((node - 1) % n) * 5, am_row
        assert am_row[1] == node * 5 + 1, am_row
        np.testing.assert_array_equal(am_row[2:], 2.0 * (1.0 + node))
    for b in BACKENDS[1:]:
        for op, want in outs["xla"].items():
            np.testing.assert_array_equal(
                outs[b][op], want, err_msg=f"{op}: {b} != xla"
            )
    return outs["xla"]


def main() -> None:
    from repro.core import collectives
    from repro.core.engine import make_engine
    from repro.kernels import gascore
    from repro.kernels import ref as kref

    N = 4
    mesh = make_mesh((N,), ("node",))

    def run(fn, *args, in_specs=None, out_specs=P("node")):
        if in_specs is None:
            in_specs = tuple(P("node") for _ in args)
        return jax.jit(
            shard_map(fn, mesh=mesh, in_specs=in_specs,
                          out_specs=out_specs, check_vma=False)
        )(*args)

    x = jnp.arange(4.0 * 8 * 128, dtype=jnp.float32).reshape(4, 8, 128)

    # ring_shift, multiple distances and dtypes
    for k in (1, 2, 3):
        for dt in (jnp.float32, jnp.bfloat16, jnp.int32):
            xx = x.astype(dt)
            y = run(lambda a: gascore.ring_shift(a, k=k, axis="node", n_nodes=N), xx)
            np.testing.assert_array_equal(
                np.asarray(y), kref.ring_shift(np.asarray(xx), k)
            )
    print("ring_shift OK")

    perm = (2, 0, 3, 1)
    y = run(lambda a: gascore.perm_put(a, dst=perm, axis="node", n_nodes=N), x)
    np.testing.assert_array_equal(np.asarray(y), kref.perm_put(np.asarray(x), perm))
    print("perm_put OK")

    # offset_put (AMLong semantics)
    seg = jnp.zeros((4, 16, 128), jnp.float32)
    data = jnp.tile(jnp.arange(4.0)[None, :, None], (4, 1, 128))
    data = data + jnp.arange(4.0)[:, None, None] * 100

    def prog(s, d):
        return gascore.offset_put(
            s[0], d[0], jnp.int32(4), k=1, axis="node", n_nodes=N
        )[None]

    y = np.asarray(run(prog, seg, data))
    for node in range(4):
        np.testing.assert_allclose(y[node, 4:8], np.asarray(data)[(node - 1) % 4])
        np.testing.assert_allclose(y[node, :4], 0)
    print("offset_put OK")

    # fused ring collectives vs oracles
    xl = jnp.arange(4.0 * 2 * 128).reshape(4, 2, 128)
    y = run(lambda a: gascore.ring_all_gather(a[0], axis="node", n_nodes=N)[None],
            xl, in_specs=(P("node"),))
    np.testing.assert_allclose(np.asarray(y), kref.all_gather(np.asarray(xl)))
    print("ring_all_gather OK")

    xf = jnp.arange(4.0 * 8 * 128).reshape(4, 8, 128) / 100.0
    y = run(lambda a: gascore.ring_reduce_scatter(a[0], axis="node", n_nodes=N)[None],
            xf, in_specs=(P("node"),))
    np.testing.assert_allclose(
        np.asarray(y), kref.reduce_scatter(np.asarray(xf)), rtol=1e-6
    )
    print("ring_reduce_scatter OK")

    # ---- engine parity: the paper's software<->hardware migration claim ----
    # "xla,gascore" is the heterogeneous EngineMap — alternating software
    # and hardware ranks in one mesh — and must pass the same parity suite
    # as each homogeneous engine.
    for op in ("all_reduce", "all_to_all", "all_gather", "reduce_scatter"):
        def make_prog(backend, op=op):
            def prog(a):
                e = make_engine(backend, "node", N)
                arg = a[0] if op != "all_gather" else a[0, :2]
                return getattr(e, op)(arg)[None]
            return prog

        outs = [
            np.asarray(run(make_prog(b), xf, in_specs=(P("node"),)))
            for b in BACKENDS
        ]
        for b, o in zip(BACKENDS[1:], outs[1:]):
            np.testing.assert_allclose(
                outs[0], o, rtol=1e-6, err_msg=f"{op} parity vs {b}"
            )
    print("engine parity OK (incl. heterogeneous map)")

    # ring algorithms built on top run on EVERY engine identically,
    # monolithic and segmented/pipelined (the scheduler's bulk tier)
    from repro.core import sched

    def coll_prog(backend):
        def prog(a):
            e = make_engine(backend, "node", N)
            mono = collectives.ring_all_reduce(e, a[0])
            seg = collectives.segmented_ring_all_reduce(
                e, a[0], n_segments=3, depth=2
            )
            planned = sched.all_reduce(e, a[0])
            return mono[None], seg[None], planned[None]
        return prog

    outs = {
        b: tuple(
            np.asarray(y)
            for y in run(coll_prog(b), xf, in_specs=(P("node"),),
                         out_specs=(P("node"),) * 3)
        )
        for b in BACKENDS
    }
    for b in BACKENDS:
        mono, seg, planned = outs[b]
        np.testing.assert_allclose(mono, seg, rtol=1e-6,
                                   err_msg=f"segmented != monolithic on {b}")
        np.testing.assert_allclose(mono, planned, rtol=1e-5,
                                   err_msg=f"planned != monolithic on {b}")
        np.testing.assert_allclose(mono, outs["xla"][0], rtol=1e-6,
                                   err_msg=f"ring parity vs {b}")
    print("collectives-on-engines parity OK (monolithic/segmented/planned)")

    # split-phase primitives + the collectives built on them (Extended API)
    def nb_prog(backend):
        def prog(a):
            e = make_engine(backend, "node", N)
            pending = e.shift_nb(a[0], 1)   # initiate
            local = a[0] * 2.0              # overlapped compute
            shifted = pending.wait()        # sync point
            bc = collectives.broadcast(e, a[0], root=1)
            ex = collectives.exchange(e, a[0])
            return (shifted + 0.0 * local)[None], bc[None], ex[None]
        return prog

    specs3 = (P("node"), P("node"), P("node"))
    sw = run(nb_prog("xla"), xf, in_specs=(P("node"),), out_specs=specs3)
    hw = run(nb_prog("gascore"), xf, in_specs=(P("node"),), out_specs=specs3)
    for name, a, b in zip(("shift_nb", "broadcast", "exchange"), sw, hw):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
    # broadcast oracle: every node holds node 1's contribution
    np.testing.assert_allclose(
        np.asarray(sw[1]), np.tile(np.asarray(xf)[1], (N, 1, 1))
    )
    print("split-phase primitives parity OK")

    # ---- vectored get parity: m slices per request/reply pair -------------
    from repro.core import am, gasnet

    mesh_n = make_mesh((N,), ("node",))

    def run_getv(backend):
        ctx_v = gasnet.Context(mesh_n, node_axis="node", backend=backend)

        def prog(node, seg):
            # plain vectored fetch from the left neighbour
            h = node.get_nbv(seg, frm=gasnet.Shift(1),
                             indices=[4, 0, 12], size=3)
            plain = node.sync(h)
            # pred-gated: odd ranks trace the fetch but keep zeros
            gated = node.get_v(seg, frm=gasnet.Shift(2), indices=[8, 2],
                               size=2, pred=(node.my_id % 2) == 0)
            return plain[None], gated[None]

        seg = jnp.arange(4.0 * 16).reshape(4, 16)
        return tuple(
            np.asarray(o)
            for o in ctx_v.spmd(prog, seg, out_specs=(P("node"),) * 2)
        )

    getv = {b: run_getv(b) for b in BACKENDS}
    segv = np.arange(4.0 * 16).reshape(4, 16)
    plain, gated = getv["xla"]
    for node in range(N):
        want = np.stack(
            [segv[(node + 1) % N, i : i + 3] for i in (4, 0, 12)]
        )
        np.testing.assert_allclose(plain[node], want)
        if node % 2 == 0:
            want2 = np.stack([segv[(node + 2) % N, i : i + 2] for i in (8, 2)])
            np.testing.assert_allclose(gated[node], want2)
        else:
            np.testing.assert_allclose(gated[node], 0.0)
    for b in BACKENDS[1:]:
        for name, a, o in zip(("plain", "pred-gated"), getv["xla"], getv[b]):
            np.testing.assert_allclose(
                a, o, err_msg=f"get_nbv parity vs {b}: {name}"
            )
    print("vectored get parity OK (xla/gascore/mixed, incl. pred-gated)")

    # ---- vectored put parity: m writes + command block per transfer -------
    def run_putv(backend):
        ctx_p = gasnet.Context(mesh_n, node_axis="node", backend=backend)

        def prog(node, seg):
            datas = jnp.stack(
                [jnp.full((3,), 1.0 + 10 * node.my_id + j) for j in range(2)]
            )
            h = node.put_nbv(seg, datas, to=gasnet.Shift(1),
                             indices=[1, 9],
                             pred=[True, (node.my_id % 2) == 0])
            seg = node.sync(h)
            return node.put_v(seg, jnp.full((1, 2), 77.0),
                              to=gasnet.Shift(2), indices=[13])

        seg = jnp.zeros((N, 16), jnp.float32)
        return np.asarray(ctx_p.spmd(prog, seg))

    putv = {b: run_putv(b) for b in BACKENDS}
    ref_pv = putv["xla"]
    for node in range(N):
        src = (node - 1) % N
        np.testing.assert_allclose(ref_pv[node, 1:4], 1.0 + 10 * src)
        if src % 2 == 0:
            np.testing.assert_allclose(ref_pv[node, 9:12], 2.0 + 10 * src)
        else:
            np.testing.assert_allclose(ref_pv[node, 9:12], 0.0)
        np.testing.assert_allclose(ref_pv[node, 13:15], 77.0)
    for b in BACKENDS[1:]:
        np.testing.assert_allclose(
            ref_pv, putv[b], err_msg=f"put_nbv parity vs {b}"
        )
    print("vectored put parity OK (xla/gascore/mixed, incl. per-page pred)")

    # ---- AM request/reply parity: software vs hardware vs mixed nodes -----

    def run_request_reply(backend):
        ctx_rr = gasnet.Context(mesh_n, node_axis="node", backend=backend,
                                am_payload_width=4)
        table = ctx_rr.handlers

        def pong(state, payload, args):
            out = dict(state)
            out["ack_payload"] = payload
            out["ack_arg"] = state["ack_arg"] + args[0]
            return out

        pong_id = table.register("pong", pong)

        def ping(state, payload, args):
            out = dict(state)
            out["got"] = state["got"] + args[0]
            reply = am.reply_medium(
                pong_id, payload * 2.0, args=(args[0] + 1,)
            )
            return out, reply

        table.register("ping", ping, replies=True)

        def prog_rr(node, seg):
            me = node.my_id
            state = {
                "got": jnp.zeros((), jnp.int32),
                "ack_arg": jnp.zeros((), jnp.int32),
                "ack_payload": jnp.zeros((4,), jnp.float32),
            }
            h = node.am_call(
                (me + 1) % N, "ping",
                payload=jnp.full((4,), 1.0 + me, jnp.float32),
                args=(me * 5,), ack=lambda st: st["ack_payload"],
            )
            state = node.am_flush(state)
            acked = node.sync(h)
            return (state["got"][None], state["ack_arg"][None],
                    acked[None])

        seg = jnp.zeros((N, 8), jnp.float32)
        return tuple(
            np.asarray(o) for o in ctx_rr.spmd(
                prog_rr, seg, out_specs=(P("node"),) * 3
            )
        )

    rr = {b: run_request_reply(b) for b in BACKENDS}
    got, ack_arg, acked = rr["xla"]
    for node in range(N):
        assert int(got[node]) == ((node - 1) % N) * 5
        assert int(ack_arg[node]) == node * 5 + 1
        np.testing.assert_allclose(acked[node], 2.0 * (1.0 + node))
    for b in BACKENDS[1:]:
        for name, a, o in zip(("got", "ack_arg", "ack_payload"),
                              rr["xla"], rr[b]):
            np.testing.assert_allclose(
                a, o, err_msg=f"request/reply parity vs {b}: {name}"
            )
    print("AM request/reply parity OK (xla/gascore/mixed)")

    # ---- TP-group all-reduce at decode-step payloads ----------------------
    # the tensor-parallel decode group's per-sub-block partial sum:
    # (B, 1, D)-shaped activations, f32 and bf16, planned by the
    # scheduler, bit-for-bit-comparable across software, hardware, and
    # mixed engine maps within dtype tolerance
    def tp_prog(backend, dt):
        def prog(a):
            e = make_engine(backend, "node", N)
            out = sched.all_reduce(e, a[0].astype(dt))
            return out.astype(jnp.float32)[None]
        return prog

    xd = jnp.arange(4.0 * 4 * 1 * 128).reshape(4, 4, 1, 128) / 29.0 - 9.0
    for dt, tol in ((jnp.float32, 1e-6), (jnp.bfloat16, 0.05)):
        want = np.tile(
            np.asarray(xd.astype(dt).astype(jnp.float32)).sum(0),
            (N, 1, 1, 1),
        )
        outs = [
            np.asarray(run(tp_prog(b, dt), xd, in_specs=(P("node"),)))
            for b in BACKENDS
        ]
        for b, o in zip(BACKENDS, outs):
            np.testing.assert_allclose(
                o, want, rtol=tol,
                err_msg=f"TP all-reduce vs numpy on {b} ({dt.__name__})",
            )
            np.testing.assert_allclose(
                o, outs[0], rtol=tol,
                err_msg=f"TP all-reduce engine parity vs {b}",
            )
    print("TP-group decode-payload all-reduce parity OK (f32+bf16)")

    engine_parity(N)
    print("bitwise engine parity OK (put/get/vectored/AM/all-reduce)")

    print("GASCORE_SUITE_PASS")


if __name__ == "__main__":
    main()
