"""Main-path kernels compiled by Mosaic for a described (not attached)
v5e:2x2 topology.

Interpret mode never checks Mosaic's tiling rules, so these compiles are
the only CPU-side guard that the kernels the chip runs still compile:
paged decode attention at qwen3-4b decode shapes, and the GAScore
remote-DMA kernels the ``gascore`` engine dispatches on a four-device
mesh.  Nothing runs; the topology is described inside a fixture so that
only the worker that runs this file loads the TPU compiler.
"""
import jax
import jax.numpy as jnp
import pytest
from jax import shard_map
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core import gasnet
from repro.kernels import gascore, mode
from repro.kernels import paged_attention as pa
from repro.launch.mesh import make_mesh

N = 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # the kernels decide interpret mode from the platform: here they must
    # build for the described chip, not for this process's CPU
    patch = pytest.MonkeyPatch()
    patch.setattr(mode, "platform", lambda: "tpu")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    jax.clear_caches()
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_was)
    patch.undo()
    jax.clear_caches()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def node_mesh(topo):
    return make_mesh((N,), ("node",), devices=topo.devices)


def _kernels(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("page_tokens", [8, 16])
def test_paged_attention_qwen3_4b_decode(one_chip, page_tokens):
    # qwen3-4b: 32 query heads, 8 KV heads of 128; a batch of 8 requests
    # over a 1024-page bf16 pool, 36-page tables (576 positions)
    B, Hq, Hkv, D, pages, width = 8, 32, 8, 128, 1025, 36

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    kv = s((pages, page_tokens, Hkv, D), jnp.bfloat16)
    compiled = pa.paged_attention.lower(
        s((B, Hq, D), jnp.bfloat16), kv, kv,
        s((B, width), jnp.int32), s((B,), jnp.int32),
    ).compile()
    assert _kernels(compiled)


def _compile_on_nodes(mesh, body, *shapes):
    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=P("node"),
                           out_specs=P("node"), check_vma=False))
    sharding = NamedSharding(mesh, P("node"))
    return fn.lower(*(
        jax.ShapeDtypeStruct(sh, dt, sharding=sharding) for sh, dt in shapes
    )).compile()


def test_gascore_ring_shift(node_mesh):
    compiled = _compile_on_nodes(
        node_mesh,
        lambda x: gascore.ring_shift(x, k=1, axis="node", n_nodes=N),
        ((N * 8, 128), jnp.float32),
    )
    assert _kernels(compiled)


def test_gascore_small_payload_stays_in_hbm(node_mesh):
    # an AM flag block: XLA places results this small in VMEM (S(1))
    # unless told otherwise, and a peer's remote DMA does not land there
    compiled = _compile_on_nodes(
        node_mesh,
        lambda x: gascore.ring_shift(x, k=1, axis="node", n_nodes=N),
        ((N, 16), jnp.int32),
    )
    kernels = [line.split("custom-call(")[0]
               for line in compiled.as_text().splitlines()
               if "tpu_custom_call" in line]
    assert kernels
    assert not any("S(1)" in result for result in kernels), kernels


def test_gascore_perm_put(node_mesh):
    compiled = _compile_on_nodes(
        node_mesh,
        lambda x: gascore.perm_put(x, dst=(2, 0, 3, 1), axis="node",
                                   n_nodes=N),
        ((N * 16, 128), jnp.bfloat16),
    )
    assert _kernels(compiled)


@pytest.mark.parametrize("op", ["get_v", "put_v"])
def test_gascore_vectored(node_mesh, op):
    ctx = gasnet.Context(node_mesh, node_axis="node", backend="gascore")

    def body(seg):
        node = ctx.make_node()
        if op == "get_v":
            return node.get_v(seg, frm=gasnet.Shift(1), indices=[0, 128],
                              size=128)
        datas = jnp.ones((2, 128), seg.dtype) * (1.0 + node.my_id)
        return node.put_v(seg, datas, to=gasnet.Shift(1), indices=[0, 256],
                          pred=[True, node.my_id % 2 == 0])

    compiled = _compile_on_nodes(
        node_mesh, body, ((N, 512), jnp.float32)
    )
    assert _kernels(compiled)
