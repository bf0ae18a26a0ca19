"""Extended API (split-phase non-blocking RMA) handle mechanics.

Single-device fast checks: handle lifecycle, FIFO sync_all, blocking ==
nb+sync equivalence on a 1-node mesh.  Multi-node semantics and xla/gascore
engine parity live in the subprocess suites (testing/gas_suite.py,
testing/gascore_suite.py via tests/test_multidev.py).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import extended, gasnet
from repro.core.engine import Pending, XlaEngine
from repro.launch.mesh import make_mesh


def make_ctx():
    mesh = make_mesh((1,), ("node",))
    return gasnet.Context(mesh, node_axis="node", backend="xla")


def test_pending_wait_returns_value():
    p = Pending(jnp.arange(4.0))
    np.testing.assert_allclose(np.asarray(p.wait()), np.arange(4.0))
    assert p.ready()


def test_put_handle_lands_payload_at_offset():
    local = jnp.zeros((8,), jnp.float32)
    h = extended.PutHandle(
        local,
        moved=jnp.array([1.0, 2.0]),
        midx=jnp.int32(3),
        received=jnp.array(True),
        restore=lambda x: x,
    )
    out = np.asarray(h.complete())
    np.testing.assert_allclose(out, [0, 0, 0, 1, 2, 0, 0, 0])


def test_put_handle_without_arrival_is_noop():
    local = jnp.ones((4,), jnp.float32)
    h = extended.PutHandle(
        local,
        moved=jnp.array([9.0]),
        midx=jnp.int32(0),
        received=jnp.array(False),  # no sender targeted this node
        restore=lambda x: x,
    )
    np.testing.assert_allclose(np.asarray(h.complete()), 1.0)


def test_handle_syncs_exactly_once():
    h = extended.GetHandle(jnp.zeros((2,)))
    h.complete()
    with pytest.raises(RuntimeError, match="already synced"):
        h.complete()


def test_node_sync_all_is_fifo():
    ctx = make_ctx()
    aspace = ctx.address_space()
    aspace.register("buf", (8,), jnp.float32)
    seg = aspace.alloc("buf", init_fn=jnp.ones)

    def prog(node, seg):
        node.put_nb(seg, jnp.full((2,), 5.0), index=0)
        node.get_nb(seg, index=4, size=2)
        seg2, got = node.sync_all()
        assert not node._outstanding
        return seg2, got[None]

    seg2, got = ctx.spmd(prog, seg, out_specs=(P("node"), P("node")))
    np.testing.assert_allclose(np.asarray(seg2)[0, :2], 5.0)
    np.testing.assert_allclose(np.asarray(got)[0], 1.0)


def test_multiple_outstanding_puts_compose():
    """GASNet permits several puts in flight: syncing them FIFO must land
    every write, not just the last-synced one."""
    ctx = make_ctx()
    aspace = ctx.address_space()
    aspace.register("buf", (8,), jnp.float32)
    seg = aspace.alloc("buf")

    def prog(node, seg):
        h1 = node.put_nb(seg, jnp.full((2,), 1.0), index=0)
        h2 = node.put_nb(seg, jnp.full((2,), 2.0), index=4)
        seg = node.sync(h1)
        seg = node.sync(h2)
        return seg

    out = np.asarray(ctx.spmd(prog, seg))[0]
    np.testing.assert_allclose(out, [1, 1, 0, 0, 2, 2, 0, 0])

    def prog_all(node, seg):
        node.put_nb(seg, jnp.full((2,), 3.0), index=0)
        node.put_nb(seg, jnp.full((2,), 4.0), index=2)
        node.put_nb(seg, jnp.full((2,), 5.0), index=4)
        s1, s2, s3 = node.sync_all()
        return s3

    out = np.asarray(ctx.spmd(prog_all, seg))[0]
    np.testing.assert_allclose(out, [3, 3, 4, 4, 5, 5, 0, 0])


def test_sequential_blocking_puts_stay_independent():
    """Two blocking puts issued from the SAME input array are separate
    one-sided writes to separate result values (seed semantics), not a
    chain — only *outstanding* nb puts compose."""
    ctx = make_ctx()
    aspace = ctx.address_space()
    aspace.register("buf", (4,), jnp.float32)
    seg = aspace.alloc("buf")

    def prog(node, seg):
        a = node.put(seg, jnp.full((2,), 1.0), index=0)
        b = node.put(seg, jnp.full((2,), 2.0), index=2)
        return a, b

    a, b = ctx.spmd(prog, seg, out_specs=(P("node"), P("node")))
    np.testing.assert_allclose(np.asarray(a)[0], [1, 1, 0, 0])
    np.testing.assert_allclose(np.asarray(b)[0], [0, 0, 2, 2])


def test_blocking_equals_nb_plus_sync():
    ctx = make_ctx()
    aspace = ctx.address_space()
    aspace.register("buf", (8,), jnp.float32)
    seg = aspace.alloc("buf")

    def prog_blocking(node, seg):
        return node.put(seg, jnp.arange(3.0), index=2)

    def prog_nb(node, seg):
        h = node.put_nb(seg, jnp.arange(3.0), index=2)
        _ = jnp.ones((4, 4)) @ jnp.ones((4, 4))  # overlapped compute
        return node.sync(h)

    a = np.asarray(ctx.spmd(prog_blocking, seg))
    b = np.asarray(ctx.spmd(prog_nb, seg))
    np.testing.assert_allclose(a, b)


def test_try_sync_reports_done():
    ctx = make_ctx()
    aspace = ctx.address_space()
    aspace.register("buf", (4,), jnp.float32)
    seg = aspace.alloc("buf", init_fn=jnp.ones)

    def prog(node, seg):
        h = node.get_nb(seg, index=0, size=2)
        done, val = node.try_sync(h)
        assert done
        return val[None]

    out = ctx.spmd(prog, seg, out_specs=P("node"))
    np.testing.assert_allclose(np.asarray(out)[0], 1.0)


def test_gpipe_runs_with_explicit_engine():
    from repro.parallel.pipeline import gpipe
    from jax import shard_map

    mesh = make_mesh((1,), ("pod",))
    x = jnp.arange(4.0 * 2 * 3).reshape(4, 2, 3)  # (M, mb, d)
    w = jnp.eye(3) * 2.0

    def stage(p, xb):
        return xb @ p

    def fn(p, xm):
        eng = XlaEngine("pod", 1)
        return gpipe(stage, p, xm, axis="pod", n_stages=1, engine=eng)

    out = jax.jit(
        shard_map(fn, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
                  check_vma=False)
    )(w, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x) * 2.0)
