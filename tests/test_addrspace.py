"""AddressSpace registry + sharding sanitizer unit tests (1 device)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core.addrspace import AddressSpace, GlobalAddress
from repro.launch.mesh import make_mesh
from repro.parallel.sharding import sanitize


def _mesh1():
    return make_mesh((1,), ("node",))


def test_register_alloc_read():
    aspace = AddressSpace(_mesh1(), "node")
    spec = aspace.register("seg", (8, 4), jnp.float32)
    assert spec.local_size == 32
    assert spec.global_shape(1) == (1, 8, 4)
    seg = aspace.alloc("seg", init_fn=jnp.ones)
    assert seg.shape == (1, 8, 4)
    got = aspace.read(seg, GlobalAddress(node=0, index=3), length=5)
    np.testing.assert_allclose(np.asarray(got), 1.0)


def test_register_duplicate_rejected():
    aspace = AddressSpace(_mesh1(), "node")
    aspace.register("seg", (4,))
    with pytest.raises(ValueError):
        aspace.register("seg", (4,))


def test_alloc_from_shape_checked():
    aspace = AddressSpace(_mesh1(), "node")
    aspace.register("seg", (4,))
    with pytest.raises(ValueError):
        aspace.alloc_from("seg", jnp.zeros((1, 5)))


def test_bad_node_axis_rejected():
    with pytest.raises(ValueError):
        AddressSpace(_mesh1(), "nope")


def test_sanitize_single_and_tuple_axes():
    mesh = make_mesh((1,), ("model",))
    # size-1 axes always divide
    assert sanitize(P("model", None), (7, 3), mesh) == P("model", None)
    # unknown-dim specs pass through
    assert sanitize(P(None, None), (5,), mesh) == P(None, None)


def test_sanitize_drops_on_fake_wide_mesh():
    # emulate a 4-wide axis via devices reshape is impossible on 1 device;
    # exercise the arithmetic through a stub mesh-like object instead
    class FakeMesh:
        shape = {"model": 4, "data": 2}

    assert sanitize(P("model"), (6,), FakeMesh()) == P(None)
    assert sanitize(P("model"), (8,), FakeMesh()) == P("model")
    assert sanitize(P(("data", "model")), (8,), FakeMesh()) == P(("data", "model"))
    # tuple entry: drop trailing axes until it divides (8 % 8 != 0 -> try
    # ("data",): 6 % 2 == 0)
    assert sanitize(P(("data", "model")), (6,), FakeMesh()) == P(("data",))
    assert sanitize(P(("data", "model")), (3,), FakeMesh()) == P(None)
