"""Tensor-parallel decode suite: planned TP-group all-reduce parity over
mixed engine maps, head-sharded paged decode servers, and TP decode
groups inside the disaggregated cluster (3 devices)."""
import os

if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=3"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.launch.mesh import make_mesh


def main() -> None:
    from repro.configs.registry import SMOKE
    from repro.core import sched
    from repro.core.engine import make_engine
    from repro.launch.serve import PagedServer, Request, TPPagedServer
    from repro.models.build import build_model
    from repro.parallel.ctx import RunCtx
    from repro.serving.disagg import DisaggCluster

    # ---- TP-group all-reduce parity at decode-step payloads ----------------
    # a 2-rank TP group over a ("tp",) mesh — the exact shape and axis the
    # sharded decode step uses — with the planned collective, on pure
    # software, pure hardware, and heterogeneous engine maps.  At 2 ranks
    # every schedule is one exchange-and-add, so parity is BITWISE.
    TP = 2
    mesh = make_mesh((TP,), ("tp",))

    def ar_prog(backend, dt):
        def prog(x):
            e = make_engine(backend, "tp", TP)
            return sched.all_reduce(e, x[0].astype(dt))[None]

        return jax.jit(shard_map(prog, mesh=mesh, in_specs=(P("tp"),),
                                 out_specs=P("tp"), check_vma=False))

    for dt in (jnp.float32, jnp.bfloat16):
        x = (jnp.arange(2.0 * 4 * 1 * 128).reshape(2, 4, 1, 128) / 37.0
             - 5.0).astype(jnp.float32)
        want = np.asarray(
            x[0].astype(dt) + x[1].astype(dt), np.float32
        )
        outs = {
            b: np.asarray(ar_prog(b, dt)(x)).astype(np.float32)
            for b in ("xla", "gascore", "xla,gascore")
        }
        for b, o in outs.items():
            np.testing.assert_array_equal(
                o[0], o[1], err_msg=f"all-reduce not replicated on {b}"
            )
            np.testing.assert_array_equal(
                o[0], want, err_msg=f"all-reduce != sum on {b} ({dt})"
            )
    print("TP all-reduce parity OK (xla/gascore/mixed, f32+bf16, bitwise)")

    # ---- head-sharded paged decode server: token parity vs tp=1 ------------
    cfg = SMOKE["qwen3-4b"]
    model = build_model(cfg)
    ctx = RunCtx(mesh=None, remat="none")
    params, _ = model.init(ctx, jax.random.PRNGKey(0))

    rng = np.random.default_rng(11)
    shared = rng.integers(0, cfg.vocab, 9).tolist()
    reqs = []
    for rid in range(5):
        prompt = (shared + rng.integers(0, cfg.vocab, 3).tolist()
                  if rid % 2 == 0 else
                  rng.integers(0, cfg.vocab, int(rng.integers(5, 12))).tolist())
        reqs.append((rid, prompt, int(rng.integers(4, 8))))

    def run_server(server_cls, **kw):
        srv = server_cls(model, ctx, params, batch_size=3, cache_len=32,
                         page_tokens=8, n_pool_pages=14, **kw)
        for rid, prompt, mx in reqs:
            srv.submit(Request(rid=rid, prompt=list(prompt), max_new=mx))
        for _ in range(400):
            if len(srv.finished) == len(reqs):
                break
            srv.step()
        assert len(srv.finished) == len(reqs), "server stalled"
        return {r.rid: list(r.out) for r in srv.finished}

    base = run_server(PagedServer)
    for backend in ("xla", "xla,gascore"):
        toks = run_server(TPPagedServer, tp=2, tp_backend=backend)
        for rid, want in base.items():
            assert toks[rid] == want, (backend, rid, toks[rid], want)
    print("TPPagedServer token parity OK (tp=2, xla + mixed map)")

    # ---- TP decode group inside the disaggregated cluster ------------------
    def run_cluster(**kw):
        cl = DisaggCluster(model, ctx, params, n_prefill=1, decode_batch=2,
                           cache_len=32, page_tokens=8, paged=True, **kw)
        for rid, prompt, mx in reqs:
            cl.submit(Request(rid=rid, prompt=list(prompt), max_new=mx))
        stats = cl.run_until_drained(max_ticks=500)
        return {r.rid: list(r.out) for r in cl.finished}, stats

    cbase, _ = run_cluster(n_decode=1)
    ctp, stats = run_cluster(n_decode=2, tp=2, tp_backend="xla,gascore")
    assert stats["tp"] == 2 and stats["n_decode_groups"] == 1
    assert stats["kv_acked"] == len(reqs)
    for rid, want in cbase.items():
        assert ctp[rid] == want, (rid, ctp[rid], want)
    print("DisaggCluster TP decode group parity OK (1 prefill + tp=2 group)")

    print("TP_SUITE_PASS")


if __name__ == "__main__":
    main()
