"""GAS layer suite: segments, put/get, AMs, ring collectives (8 devices)."""
import os

if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.launch.mesh import make_mesh


def main() -> None:
    from repro.core import am, collectives, gasnet
    from repro.core.engine import make_engine

    mesh = make_mesh((8,), ("node",))
    ctx = gasnet.Context(mesh, node_axis="node", backend="xla",
                         am_payload_width=4)

    aspace = ctx.address_space()
    aspace.register("buf", (32,), jnp.float32)
    seg = aspace.alloc("buf")
    assert seg.shape == (8, 32)

    # ---- one-sided put (Shift pattern, sender-chosen offset) ------------
    def prog(node, seg):
        data = jnp.full((4,), node.my_id, jnp.float32)
        seg = node.put(seg, data, to=gasnet.Shift(1), index=2)
        node.barrier()
        return seg

    got = np.asarray(ctx.spmd(prog, seg))
    for n in range(8):
        np.testing.assert_allclose(got[n, 2:6], (n - 1) % 8)
        np.testing.assert_allclose(got[n, :2], 0)
    print("put OK")

    # ---- put with Perm pattern ------------------------------------------
    perm = (3, 0, 7, 1, 2, 6, 5, 4)

    def prog_perm(node, seg):
        data = jnp.full((4,), node.my_id, jnp.float32)
        return node.put(seg, data, to=gasnet.Perm(perm), index=0)

    got = np.asarray(ctx.spmd(prog_perm, seg))
    for s, d in enumerate(perm):
        np.testing.assert_allclose(got[d, :4], s)
    print("perm put OK")

    # ---- one-sided get ----------------------------------------------------
    seg2 = ctx.spmd(prog, seg)

    def prog_get(node, seg):
        return node.get(seg, frm=gasnet.Shift(3), index=2, size=4)[None]

    out = np.asarray(ctx.spmd(prog_get, seg2, out_specs=P("node")))
    for n in range(8):
        np.testing.assert_allclose(out[n], (n + 3 - 1) % 8)
    print("get OK")

    # ---- ring collectives vs natives --------------------------------------
    x = jnp.arange(8.0 * 16).reshape(8, 16)

    def prog_coll(node, x):
        e = node.engine
        ag = collectives.ring_all_gather(e, node.local(x))
        rs = collectives.ring_reduce_scatter(e, ag)
        ar = collectives.ring_all_reduce(e, node.local(x) * 1.0)
        return ag[None], rs[None], ar[None]

    ag, rs, ar = ctx.spmd(
        prog_coll, x, out_specs=(P("node"), P("node"), P("node"))
    )
    ag, rs, ar = map(np.asarray, (ag, rs, ar))
    xg = np.asarray(x)
    for n in range(8):
        np.testing.assert_allclose(ag[n], xg.reshape(-1))
        np.testing.assert_allclose(rs[n], 8 * xg.reshape(8, 16)[n])
    np.testing.assert_allclose(ar, np.tile(xg.sum(0), (8, 1)))
    print("ring collectives OK")

    # ---- hierarchical all-reduce (2 pods x 4) -----------------------------
    mesh2 = make_mesh((2, 4), ("pod", "data"))
    xx = jnp.arange(8.0 * 16).reshape(2, 4, 16) / 7.0

    def prog_hier(x_l):
        inner = make_engine("xla", "data", 4)
        outer = make_engine("xla", "pod", 2)
        return collectives.hierarchical_all_reduce(inner, outer, x_l[0, 0])[
            None, None
        ]

    hier = jax.jit(
        shard_map(
            prog_hier, mesh=mesh2, in_specs=(P("pod", "data"),),
            out_specs=P("pod", "data"), check_vma=False,
        )
    )(xx)
    np.testing.assert_allclose(
        np.asarray(hier), np.tile(np.asarray(xx).sum((0, 1)), (2, 4, 1)),
        rtol=1e-6,
    )
    print("hierarchical all-reduce OK")

    # ---- active messages: counters + AMLong writes -------------------------
    handlers = ctx.handlers

    @handlers.handler("count")
    def h_count(state, payload, args):
        out = dict(state)
        out["cnt"] = state["cnt"] + args[0]
        return out

    handlers.register("write", am.long_write_handler("buf"))

    def prog_am(node, seg):
        state = {"cnt": jnp.zeros((), jnp.int32), "buf": node.local(seg)}
        d1 = jnp.asarray((node.my_id + 2) % 8, jnp.int32)
        node.am_short(d1, "count", args=(3,))
        node.am_short(d1, "count", args=(4,))
        node.am_long(
            jnp.asarray((node.my_id + 1) % 8, jnp.int32), "write",
            payload=jnp.full((4,), 100 + node.my_id, jnp.float32),
            dst_index=8,
        )
        state = node.am_flush(state)
        return state["cnt"][None], state["buf"][None]

    cnt, buf = ctx.spmd(prog_am, seg, out_specs=(P("node"), P("node")))
    cnt, buf = np.asarray(cnt), np.asarray(buf)
    np.testing.assert_array_equal(cnt, 7)
    for n in range(8):
        np.testing.assert_allclose(buf[n, 8:12], 100 + (n - 1) % 8)
    print("active messages OK")

    # ---- AM overflow accounting -------------------------------------------
    ctx2 = gasnet.Context(mesh, node_axis="node", backend="xla",
                          am_payload_width=4, am_capacity=4,
                          am_per_peer_capacity=1)
    ctx2.handlers.register("count", h_count)

    def prog_over(node, seg):
        state = {"cnt": jnp.zeros((), jnp.int32)}
        d = jnp.asarray((node.my_id + 1) % 8, jnp.int32)
        for _ in range(3):  # 3 messages to the same peer, capacity 1
            node.am_short(d, "count", args=(1,))
        state = node.am_flush(state)
        return state["cnt"][None], node.dropped[None]

    cnt, dropped = ctx2.spmd(prog_over, seg, out_specs=(P("node"), P("node")))
    np.testing.assert_array_equal(np.asarray(cnt), 1)
    np.testing.assert_array_equal(np.asarray(dropped), 2)
    print("AM overflow accounting OK")

    # ---- AM request/reply: round trip + ack handles, xla vs mixed map ------
    def run_request_reply(backend):
        ctx_rr = gasnet.Context(mesh, node_axis="node", backend=backend,
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
                pong_id, payload + 1.0, args=(args[0] + 1,)
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
                (me + 3) % 8, "ping",
                payload=jnp.full((4,), me, jnp.float32),
                args=(me * 10,), ack=lambda st: st["ack_payload"],
            )
            state = node.am_flush(state)
            acked = node.sync(h)
            return (state["got"][None], state["ack_arg"][None],
                    acked[None])

        return tuple(
            np.asarray(o) for o in ctx_rr.spmd(
                prog_rr, seg, out_specs=(P("node"),) * 3
            )
        )

    rr_sw = run_request_reply("xla")
    got, ack_arg, acked = rr_sw
    for n in range(8):
        assert int(got[n]) == ((n - 3) % 8) * 10          # request landed
        assert int(ack_arg[n]) == n * 10 + 1              # reply came back
        np.testing.assert_allclose(acked[n], n + 1.0)     # ack handle value
    rr_mix = run_request_reply("xla,gascore")
    for name, a, b in zip(("got", "ack_arg", "ack_payload"), rr_sw, rr_mix):
        np.testing.assert_allclose(a, b, err_msg=f"request/reply: {name}")
    print("AM request/reply round trip OK (xla + mixed map)")

    # ---- Extended API: split-phase non-blocking put/get --------------------
    def prog_nb(node, seg):
        # initiate, overlap independent compute, then sync
        h = node.put_nb(seg, jnp.full((4,), node.my_id, jnp.float32),
                        to=gasnet.Shift(1), index=2)
        overlapped = jnp.sum(node.local(seg) * 2.0)  # no dep on the transfer
        seg = node.sync(h)
        g = node.get_nb(seg, frm=gasnet.Shift(3), index=2, size=4)
        done, got = node.try_sync(g)
        assert done
        return seg, got[None] + 0.0 * overlapped

    seg_nb, got = ctx.spmd(prog_nb, seg, out_specs=(P("node"), P("node")))
    seg_blk = ctx.spmd(prog, seg)  # the blocking version of the same put
    np.testing.assert_allclose(np.asarray(seg_nb), np.asarray(seg_blk))
    for n in range(8):
        np.testing.assert_allclose(np.asarray(got)[n], (n + 3 - 1) % 8)
    print("nb put/get OK")

    # ---- vectored get (get_nbv): one request/reply pair for m slices -------
    def prog_nbv(node, seg):
        # gated fetch: odd ranks trace the same transfers but get zeros
        h = node.get_nbv(seg, frm=gasnet.Shift(1), indices=[2, 6, 0],
                         size=2, pred=(node.my_id % 2) == 0)
        gated = node.sync(h)
        # ungated fetch via the blocking wrapper
        allv = node.get_v(seg, frm=gasnet.Shift(3), indices=[4, 0], size=3)
        return gated[None], allv[None]

    seg_src = ctx.spmd(prog, seg)  # deterministic contents (put suite above)
    gated, allv = map(
        np.asarray,
        ctx.spmd(prog_nbv, seg_src, out_specs=(P("node"), P("node"))),
    )
    src_seg = np.asarray(seg_src)
    for n in range(8):
        want = np.stack([src_seg[(n + 1) % 8, i : i + 2] for i in (2, 6, 0)])
        if n % 2 == 0:
            np.testing.assert_allclose(gated[n], want)
        else:
            np.testing.assert_allclose(gated[n], 0.0)
        want3 = np.stack([src_seg[(n + 3) % 8, i : i + 3] for i in (4, 0)])
        np.testing.assert_allclose(allv[n], want3)
    print("vectored get (incl. pred-gated) OK")

    # ---- vectored put (put_nbv): m writes + offsets in one command block ---
    def prog_pnbv(node, seg):
        datas = jnp.stack(
            [jnp.full((3,), 1.0 + node.my_id * 10 + j) for j in range(2)]
        )
        # per-payload flags: payload 1 ships gated-off from odd ranks
        h = node.put_nbv(seg, datas, to=gasnet.Shift(1), indices=[2, 20],
                         pred=[True, (node.my_id % 2) == 0])
        overlapped = jnp.sum(node.local(seg))  # no dep on the transfer
        seg = node.sync(h)
        # blocking wrapper, Perm pattern
        seg = node.put_v(seg, jnp.full((1, 4), 70.0 + node.my_id),
                         to=gasnet.Perm(perm), indices=[10])
        return seg + 0.0 * overlapped

    zseg = aspace.alloc("buf")
    got = np.asarray(ctx.spmd(prog_pnbv, zseg))
    for n in range(8):
        src = (n - 1) % 8
        np.testing.assert_allclose(got[n, 2:5], 1.0 + src * 10)
        if src % 2 == 0:
            np.testing.assert_allclose(got[n, 20:23], 2.0 + src * 10)
        else:
            np.testing.assert_allclose(got[n, 20:23], 0.0)
        np.testing.assert_allclose(got[n, 10:14], 70.0 + perm.index(n))
    print("vectored put (incl. per-page pred) OK")

    def prog_nb_all(node, seg):
        node.put_nb(seg, jnp.full((2,), 1.0, jnp.float32),
                    to=gasnet.Shift(1), index=0)
        node.get_nb(seg, frm=gasnet.Shift(1), index=2, size=2)
        seg2, got = node.sync_all()  # FIFO completion
        return seg2, got[None]

    seg_all, _ = ctx.spmd(prog_nb_all, seg, out_specs=(P("node"), P("node")))
    np.testing.assert_allclose(np.asarray(seg_all)[:, :2], 1.0)
    print("sync_all OK")

    # ---- new collectives: broadcast + exchange (all-to-all) ----------------
    def prog_bcex(node, x):
        e = node.engine
        bc = collectives.broadcast(e, node.local(x), root=5)
        ex = collectives.exchange(e, node.local(x))
        return bc[None], ex[None]

    bc, ex = ctx.spmd(prog_bcex, x, out_specs=(P("node"), P("node")))
    bc, ex = np.asarray(bc), np.asarray(ex)
    for n in range(8):
        np.testing.assert_allclose(bc[n], xg[5])
    np.testing.assert_allclose(
        ex.reshape(8, 8, 2), xg.reshape(8, 8, 2).transpose(1, 0, 2)
    )
    print("broadcast/exchange OK")

    # ---- engine parity (xla vs gascore) for every Extended op --------------
    ctx_hw = gasnet.Context(mesh, node_axis="node", backend="gascore")
    xk = jnp.arange(8.0 * 8 * 128).reshape(8, 8, 128)
    aspace_hw = ctx_hw.address_space()
    aspace_hw.register("kbuf", (8, 128), jnp.float32)
    segk = aspace_hw.alloc("kbuf")

    def prog_ext(node, seg, x):
        h = node.put_nb(seg, jnp.full((128,), 1.0 + node.my_id, jnp.float32),
                        to=gasnet.Shift(1), index=128)
        seg = node.sync(h)
        g = node.get_nb(seg, frm=gasnet.Shift(1), index=128, size=128)
        got = node.sync(g)
        # vectored multi-get, gated on even ranks: both engines must agree
        gv = node.get_nbv(seg, frm=gasnet.Shift(2), indices=[128, 0, 192],
                          size=64, pred=(node.my_id % 2) == 0)
        gotv = node.sync(gv)
        # vectored multi-put (per-payload flags): the write-side mirror
        pv = node.put_nbv(
            seg,
            [jnp.full((32,), 5.0 + node.my_id), jnp.full((32,), 9.0)],
            to=gasnet.Shift(3), indices=[256, 640],
            pred=[True, (node.my_id % 2) == 0],
        )
        seg = node.sync(pv)
        e = node.engine
        bc = collectives.broadcast(e, node.local(x), root=2)
        ex = collectives.exchange(e, node.local(x))
        return seg, got[None], gotv[None], bc[None], ex[None]

    specs = (P("node"),) * 5
    sw = ctx.spmd(prog_ext, segk, xk, out_specs=specs)
    hw = ctx_hw.spmd(prog_ext, segk, xk, out_specs=specs)
    for name, a, b in zip(("put_nb/put_nbv/sync", "get_nb", "get_nbv(pred)",
                           "broadcast", "exchange"), sw, hw):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6,
            err_msg=f"engine parity: {name}",
        )
    print("extended engine parity OK (incl. vectored get + put)")

    # ---- heterogeneous EngineMap: mixed sw/hw nodes, same parity suite -----
    # Alternating software (XLA) and hardware (GAScore) ranks in ONE mesh:
    # the paper's mixed cluster.  The identical Extended-API program must
    # produce identical results.
    ctx_mix = gasnet.Context(mesh, node_axis="node", backend="xla,gascore")
    mix = ctx_mix.spmd(prog_ext, segk, xk, out_specs=specs)
    for name, a, b in zip(("put_nb/put_nbv/sync", "get_nb", "get_nbv(pred)",
                           "broadcast", "exchange"), sw, mix):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6,
            err_msg=f"mixed-map parity: {name}",
        )
    print("heterogeneous EngineMap parity OK")

    # ---- scheduler: segmented rings match monolithic; plans dispatch -------
    from repro.core import sched

    xi = jnp.arange(8 * 16, dtype=jnp.int32).reshape(8, 16) * 7 - 300

    def prog_seg(node, x):
        e = node.engine
        xl = node.local(x)
        mono = collectives.ring_all_reduce(e, xl)
        seg = collectives.segmented_ring_all_reduce(
            e, xl, n_segments=3, depth=2
        )
        agm = collectives.ring_all_gather(e, xl)
        ags = collectives.segmented_ring_all_gather(
            e, xl, n_segments=4, depth=3
        )
        planned = sched.all_reduce(e, xl)
        return mono[None], seg[None], agm[None], ags[None], planned[None]

    for c in (ctx, ctx_mix):
        mono, seg, agm, ags, planned = map(
            np.asarray, c.spmd(prog_seg, xi, out_specs=(P("node"),) * 5)
        )
        np.testing.assert_array_equal(mono, seg)
        np.testing.assert_array_equal(agm, ags)
        np.testing.assert_array_equal(
            planned, np.tile(np.asarray(xi).sum(0), (8, 1))
        )
    print("segmented + planned collectives OK")

    # ---- TP-group all-reduce at decode-step payloads -----------------------
    # the per-sub-block partial sum of a tensor-parallel decode group:
    # (B, 1, D)-shaped activations, f32 and bf16, must agree across pure
    # software, pure hardware, and mixed engine maps (the ISSUE's
    # heterogeneous TP groups) and match the numpy sum
    def prog_tp(node, x):
        return sched.all_reduce(node.engine, node.local(x))[None]

    for dt, tol in ((jnp.float32, 1e-6), (jnp.bfloat16, 0.05)):
        xa = (jnp.arange(8.0 * 4 * 1 * 128).reshape(8, 4, 1, 128) / 37.0
              - 5.0).astype(dt)
        want = np.tile(
            np.asarray(xa.astype(jnp.float32)).sum(0), (8, 1, 1, 1)
        )
        outs = {
            name: np.asarray(
                c.spmd(prog_tp, xa, out_specs=P("node"))
            ).astype(np.float32)
            for name, c in (("xla", ctx), ("gascore", ctx_hw),
                            ("mixed", ctx_mix))
        }
        for name, o in outs.items():
            np.testing.assert_allclose(
                o, want, rtol=tol,
                err_msg=f"TP all-reduce vs numpy on {name} ({dt.__name__})",
            )
            np.testing.assert_allclose(
                o, outs["xla"], rtol=tol,
                err_msg=f"TP all-reduce engine parity: {name}",
            )
    print("TP-group all-reduce parity OK (decode payloads, f32+bf16)")

    print("GAS_SUITE_PASS")


if __name__ == "__main__":
    main()
