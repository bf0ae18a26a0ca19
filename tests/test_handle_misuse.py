"""Split-phase handle misuse: every double-wait must raise, loudly.

GASNet's ``wait_syncnb`` on an already-synced handle is undefined
behaviour on the wire; here it is a defined error
(:class:`~repro.core.extended.AlreadyWaitedError`) so a lost handle or
a duplicated sync in host scheduling code fails the run instead of
silently re-applying (or dropping) a transfer.  Parameterised over the
software (``xla``) and hardware (``gascore`` interpret-mode) engines on
a 1-node mesh — the handle lifecycle is engine-independent and must
stay that way.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import gasnet
from repro.core.extended import AlreadyWaitedError
from repro.launch.mesh import make_mesh

ENGINES = ("xla", "gascore")


def make_ctx(backend):
    mesh = make_mesh((1,), ("node",))
    return gasnet.Context(mesh, node_axis="node", backend=backend)


def make_seg(ctx, n_el=16):
    aspace = ctx.address_space()
    aspace.register("buf", (n_el,), jnp.float32)
    return aspace.alloc("buf", init_fn=jnp.ones)


@pytest.mark.parametrize("backend", ENGINES)
def test_put_sync_after_try_sync_raises(backend):
    ctx = make_ctx(backend)
    seg = make_seg(ctx)

    def prog(node, seg):
        h = node.put_nb(seg, jnp.full((2,), 5.0), index=0)
        done, seg2 = node.try_sync(h)
        assert done  # static schedule: the poll always completes
        with pytest.raises(AlreadyWaitedError, match="already synced"):
            node.sync(h)
        return seg2

    seg2 = ctx.spmd(prog, seg, out_specs=P("node"))
    np.testing.assert_allclose(np.asarray(seg2)[0, :2], 5.0)


@pytest.mark.parametrize("backend", ENGINES)
def test_get_sync_after_try_sync_raises(backend):
    ctx = make_ctx(backend)
    seg = make_seg(ctx)

    def prog(node, seg):
        h = node.get_nb(seg, index=4, size=2)
        done, got = node.try_sync(h)
        assert done
        with pytest.raises(AlreadyWaitedError, match="already synced"):
            node.sync(h)
        return got[None]

    got = ctx.spmd(prog, seg, out_specs=P("node"))
    np.testing.assert_allclose(np.asarray(got)[0], 1.0)


@pytest.mark.parametrize("backend", ENGINES)
def test_double_sync_all_harmless_but_drained_handle_raises(backend):
    """``sync_all`` twice is legal (the second is a no-op over an empty
    outstanding list) — but manually syncing a handle the first
    ``sync_all`` already completed is the double-wait error."""
    ctx = make_ctx(backend)
    seg = make_seg(ctx)

    def prog(node, seg):
        h_put = node.put_nb(seg, jnp.full((2,), 3.0), index=0)
        node.get_nb(seg, index=8, size=2)
        first = node.sync_all()
        assert len(first) == 2
        assert node.sync_all() == []  # idempotent on an empty list
        with pytest.raises(AlreadyWaitedError, match="already synced"):
            node.sync(h_put)
        return first[0]

    seg2 = ctx.spmd(prog, seg, out_specs=P("node"))
    np.testing.assert_allclose(np.asarray(seg2)[0, :2], 3.0)


@pytest.mark.parametrize("backend", ENGINES)
def test_putv_handle_double_sync_raises(backend):
    ctx = make_ctx(backend)
    seg = make_seg(ctx)

    def prog(node, seg):
        h = node.put_nbv(
            seg, jnp.arange(4.0).reshape(2, 2), indices=[0, 8]
        )
        seg2 = node.sync(h)
        with pytest.raises(AlreadyWaitedError, match="already synced"):
            node.sync(h)
        return seg2

    seg2 = ctx.spmd(prog, seg, out_specs=P("node"))
    np.testing.assert_allclose(np.asarray(seg2)[0, :2], [0.0, 1.0])
    np.testing.assert_allclose(np.asarray(seg2)[0, 8:10], [2.0, 3.0])


@pytest.mark.parametrize("backend", ENGINES)
def test_getv_handle_double_sync_raises(backend):
    ctx = make_ctx(backend)
    seg = make_seg(ctx)

    def prog(node, seg):
        h = node.get_nbv(seg, indices=[0, 4], size=2)
        got = node.sync(h)
        with pytest.raises(AlreadyWaitedError, match="already synced"):
            node.sync(h)
        return got[None]

    got = ctx.spmd(prog, seg, out_specs=P("node"))
    assert np.asarray(got).shape == (1, 2, 2)
    np.testing.assert_allclose(np.asarray(got)[0], 1.0)
