"""hlostats collective trip-multiplication check (4 devices)."""
import os

if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.launch.mesh import make_mesh


def main() -> None:
    from repro.launch import hlostats

    mesh = make_mesh((4,), ("d",))
    M, T = 256, 10

    def f(x, ws):
        def body(c, w):
            return jax.lax.psum(c @ w, "d"), None

        y, _ = jax.lax.scan(body, x, ws)
        return y

    fn = shard_map(f, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
                       check_vma=False)
    comp = jax.jit(fn).lower(
        jax.ShapeDtypeStruct((M, M), jnp.float32),
        jax.ShapeDtypeStruct((T, M, M), jnp.float32),
    ).compile()
    st = hlostats.analyze(comp.as_text())
    expect = T * M * M * 4  # T all-reduces of (M, M) f32 operands
    assert abs(st.collective_bytes - expect) / expect < 0.05, (
        st.collective_bytes, expect)
    per = st.collective_per_type["all-reduce"]
    assert abs(per - expect) / expect < 0.05
    print("HLOSTATS_COLL_PASS")


if __name__ == "__main__":
    main()
