"""Compute-kernel benchmarks (single device).

On the CPU the Pallas kernels run in TPU-interpret mode, which measures
*semantics*, not speed (on a TPU they compile; rows name which ran) —
wall numbers quantify the oracle (jnp) path and
report kernel parity + the analytic FLOP count per call (what the roofline
uses on target hardware).  CSV: name,us_per_call,derived.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import mode, ops, ref


def timeit(fn, *args, iters=10, warmup=2):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def main() -> None:
    rng = np.random.default_rng(0)

    # flash attention (prefill hot spot)
    B, Hq, Hkv, S, D = 1, 8, 2, 1024, 128
    q = jnp.asarray(rng.normal(size=(B, Hq, S, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Hkv, S, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Hkv, S, D)), jnp.float32)
    f_ref = jax.jit(lambda a, b, c: ref.attention(a, b, c, causal=True))
    us = timeit(f_ref, q, k, v)
    flops = 4 * B * Hq * S * S * D / 2  # causal
    print(f"attention_ref_S{S},{us:.0f},{flops / (us * 1e-6) / 1e9:.1f}GFLOP/s")
    got = ops.attention(q, k, v, causal=True, impl="pallas")
    ok = bool(jnp.allclose(got, f_ref(q, k, v), atol=2e-4))
    print(f"attention_pallas_parity,0,{ok}")

    # paged attention (decode hot spot) — oracle wall time, pallas parity,
    # and the DMA-blocking knobs (pages_per_block x block_b) of the
    # batch-blocked kernel, which must be bit-identical across settings
    B, Hq, Hkv, D, T, NP, P = 8, 8, 2, 64, 8, 8, 64
    pq = jnp.asarray(rng.normal(size=(B, Hq, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(P, T, Hkv, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, T, Hkv, D)), jnp.float32)
    tbl = jnp.asarray(rng.integers(0, P, (B, NP)), jnp.int32)
    lens = jnp.asarray(rng.integers(1, NP * T + 1, (B,)), jnp.int32)
    f_pref = jax.jit(lambda *t: ref.paged_attention(*t))
    us = timeit(f_pref, pq, kp, vp, tbl, lens)
    print(f"paged_attention_ref_B{B}_NP{NP},{us:.0f},oracle")
    want = f_pref(pq, kp, vp, tbl, lens)
    base = None
    for ppb, bb in ((1, 1), (4, 4), (8, 8)):
        f_pa = jax.jit(lambda *t, _p=ppb, _b=bb: ops.paged_attention(
            *t, impl="pallas", pages_per_block=_p, block_b=_b))
        us = timeit(f_pa, pq, kp, vp, tbl, lens, iters=5)
        got = np.asarray(f_pa(pq, kp, vp, tbl, lens))
        if base is None:
            base = got
            ok = bool(np.allclose(got, np.asarray(want), atol=2e-4))
            print(f"paged_attention_pallas_parity,0,{ok}")
        else:
            # perf knobs must not change a single bit of the output
            bit = bool((got == base).all())
            print(f"paged_attention_pallas_bitinv_p{ppb}b{bb},0,{bit}")
        how = "compiled" if mode.on_tpu() else "interpret"
        print(f"paged_attention_pallas_p{ppb}b{bb},{us:.0f},{how}")

    # MoE router
    T, E, K = 4096, 64, 8
    logits = jnp.asarray(rng.normal(size=(T, E)), jnp.float32)
    f_route = jax.jit(
        lambda l: ref.route_topk(l, k=K, capacity=T // E * 2)
    )
    us = timeit(f_route, logits)
    print(f"moe_router_ref_T{T}_E{E},{us:.0f},{T / (us * 1e-6) / 1e6:.1f}Mtok/s")
    pe, ps, pw, pk = ops.moe_router(
        logits, k=K, capacity=T // E * 2, impl="pallas", block_t=512
    )
    re_, rs_, rw_, rk_ = f_route(logits)
    ok = bool(
        (np.asarray(pe) == np.asarray(re_)).all()
        and (np.asarray(ps) == np.asarray(rs_)).all()
    )
    print(f"moe_router_pallas_parity,0,{ok}")

    # selective scan
    B, S, Di, N = 1, 2048, 512, 16
    x = jnp.asarray(rng.normal(size=(B, S, Di)), jnp.float32)
    dt = jnp.asarray(rng.uniform(1e-3, 1e-1, size=(B, S, Di)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.5, 2.0, size=(Di, N)), jnp.float32)
    bmat = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
    cmat = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
    d = jnp.asarray(rng.normal(size=(Di,)), jnp.float32)
    f_scan = jax.jit(lambda *t: ref.selective_scan(*t))
    us = timeit(f_scan, x, dt, a, bmat, cmat, d)
    el = B * S * Di * N
    print(f"selective_scan_ref_S{S},{us:.0f},{el / (us * 1e-6) / 1e9:.2f}Gstate/s")

    # gated linear scan (RG-LRU)
    av = jnp.asarray(rng.uniform(0.1, 0.99, size=(B, S, Di)), jnp.float32)
    bv = jnp.asarray(rng.normal(size=(B, S, Di)), jnp.float32)
    f_lru = jax.jit(ref.gated_linear_scan)
    us = timeit(f_lru, av, bv)
    print(f"rglru_ref_S{S},{us:.0f},"
          f"{B * S * Di / (us * 1e-6) / 1e9:.2f}Gel/s")

    print("KERNEL_BENCH_DONE")


if __name__ == "__main__":
    main()
