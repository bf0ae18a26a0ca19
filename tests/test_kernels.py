"""Per-kernel allclose sweeps: Pallas (interpret) vs pure-jnp oracles.

Shape × dtype sweeps per the deliverable: every kernel is validated against
``repro.kernels.ref`` on CPU via TPU-interpret mode.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def _rand(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.normal(size=shape) * scale, dtype)


# --------------------------------------------------------------------------- #
# flash attention
# --------------------------------------------------------------------------- #
FA_CASES = [
    # (B, Hq, Hkv, S, D, causal, window, dtype)
    (2, 4, 2, 256, 64, True, None, jnp.float32),
    (1, 4, 4, 128, 128, True, None, jnp.float32),
    (2, 8, 2, 256, 64, True, 64, jnp.float32),
    (1, 2, 1, 128, 64, False, None, jnp.float32),
    (1, 4, 1, 256, 128, True, None, jnp.bfloat16),
    (1, 2, 2, 128, 64, True, 32, jnp.bfloat16),
]


@pytest.mark.parametrize("case", FA_CASES, ids=[str(c) for c in FA_CASES])
def test_flash_attention_vs_oracle(case):
    B, Hq, Hkv, S, D, causal, window, dtype = case
    q = _rand((B, Hq, S, D), dtype)
    k = _rand((B, Hkv, S, D), dtype)
    v = _rand((B, Hkv, S, D), dtype)
    got = ops.attention(q, k, v, causal=causal, window=window, impl="pallas")
    want = ref.attention(q, k, v, causal=causal, window=window)
    atol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=atol, rtol=atol,
    )


def test_flash_attention_block_shapes():
    q = _rand((1, 2, 512, 64))
    k = _rand((1, 2, 512, 64))
    v = _rand((1, 2, 512, 64))
    want = ref.attention(q, k, v, causal=True)
    for bq, bk in [(128, 128), (256, 128), (128, 256), (512, 512)]:
        got = ops.attention(
            q, k, v, causal=True, impl="pallas", block_q=bq, block_k=bk
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
        )


# --------------------------------------------------------------------------- #
# paged attention (decode through a page table)
# --------------------------------------------------------------------------- #
PA_CASES = [
    # (B, Hq, Hkv, D, page_tokens, n_pages, dtype)
    (2, 4, 2, 16, 4, 3, jnp.float32),
    (1, 2, 2, 8, 8, 2, jnp.float32),
    (3, 8, 2, 32, 16, 4, jnp.float32),
    (2, 4, 1, 64, 8, 4, jnp.bfloat16),
]


@pytest.mark.parametrize("case", PA_CASES, ids=[str(c) for c in PA_CASES])
def test_paged_attention_vs_oracle(case):
    B, Hq, Hkv, D, T, NP, dtype = case
    P = B * NP + 2  # pool bigger than any one request's table
    q = _rand((B, Hq, D), dtype)
    kp = _rand((P, T, Hkv, D), dtype)
    vp = _rand((P, T, Hkv, D), dtype)
    # scattered physical placement: tables index the pool arbitrarily
    table = jnp.asarray(
        RNG.permutation(P)[: B * NP].reshape(B, NP), jnp.int32
    )
    lengths = jnp.asarray(RNG.integers(0, NP * T + 1, size=(B,)), jnp.int32)
    got = ops.paged_attention(q, kp, vp, table, lengths, impl="pallas")
    want = ref.paged_attention(q, kp, vp, table, lengths)
    atol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=atol, rtol=atol,
    )


def test_paged_attention_matches_dense_attention():
    """Contiguous identity table + full length == dense decode attention."""
    B, Hq, Hkv, D, T, NP = 2, 4, 2, 16, 4, 4
    S = NP * T
    q = _rand((B, Hq, D))
    kd = _rand((B, Hkv, S, D))
    vd = _rand((B, Hkv, S, D))
    # pack the dense cache into per-request contiguous pages
    kp = jnp.moveaxis(kd, 1, 2).reshape(B * NP, T, Hkv, D)
    vp = jnp.moveaxis(vd, 1, 2).reshape(B * NP, T, Hkv, D)
    table = jnp.arange(B * NP, dtype=jnp.int32).reshape(B, NP)
    lengths = jnp.full((B,), S, jnp.int32)
    got = ops.paged_attention(q, kp, vp, table, lengths, impl="pallas")
    # dense oracle: non-causal single query over the whole cache
    want = ref.attention(q[:, :, None, :], kd, vd, causal=False)[:, :, 0]
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
    )


def test_paged_attention_masks_padded_pages():
    """Padded table entries (aliased to live pages) must not leak."""
    B, Hq, Hkv, D, T, NP = 1, 2, 1, 8, 4, 3
    q = _rand((B, Hq, D))
    kp = _rand((4, T, Hkv, D))
    vp = _rand((4, T, Hkv, D))
    lengths = jnp.asarray([5], jnp.int32)  # 2 live pages (partial second)
    base = jnp.asarray([[0, 1, 2]], jnp.int32)
    alias = jnp.asarray([[0, 1, 0]], jnp.int32)  # padded entry aliases page 0
    for impl in ("ref", "pallas"):
        a = ops.paged_attention(q, kp, vp, base, lengths, impl=impl)
        b = ops.paged_attention(q, kp, vp, alias, lengths, impl=impl)
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-6, rtol=1e-6
        )


# adversarial parity sweep for the batch-blocked kernel: GQA ratios, page
# counts that don't divide the DMA block, boundary lengths, and garbage in
# masked slots — every case checked against the jnp oracle
PA_ADV_CASES = [
    # (B, Hq, Hkv, NP, pages_per_block, block_b)
    (1, 1, 1, 1, 4, 4),     # B=1, MHA ratio 1, single page
    (2, 4, 1, 5, 4, 4),     # GQA 4, NP not a multiple of pages_per_block
    (3, 8, 1, 3, 2, 2),     # GQA 8, odd page count, B not multiple of blk_b
    (5, 8, 2, 7, 4, 2),     # odd B, NP=7 vs ppb=4 (partial last burst)
    (2, 8, 8, 2, 1, 1),     # ratio 1 with many heads, degenerate blocking
]


@pytest.mark.parametrize(
    "case", PA_ADV_CASES, ids=[str(c) for c in PA_ADV_CASES]
)
def test_paged_attention_adversarial_parity(case):
    B, Hq, Hkv, NP, ppb, bb = case
    D, T = 16, 4
    P = B * NP + 2
    q = _rand((B, Hq, D))
    kp = _rand((P, T, Hkv, D))
    vp = _rand((P, T, Hkv, D))
    table = jnp.asarray(
        RNG.permutation(P)[: B * NP].reshape(B, NP), jnp.int32
    )
    # boundary lengths: 0, 1, exactly one page, exact page multiple, full
    edge = [0, 1, T, min(2 * T, NP * T), NP * T]
    lengths = jnp.asarray((edge * ((B + 4) // 5))[:B], jnp.int32)
    got = ops.paged_attention(
        q, kp, vp, table, lengths, impl="pallas",
        pages_per_block=ppb, block_b=bb,
    )
    want = ref.paged_attention(q, kp, vp, table, lengths)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-6, rtol=2e-6
    )
    # a row at length 0 attends to nothing: output must be exactly zero
    zero_rows = np.asarray(lengths) == 0
    if zero_rows.any():
        assert (np.asarray(got)[zero_rows] == 0).all()


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_paged_attention_nan_in_masked_slots(impl):
    """NaN/Inf garbage behind ``lengths`` and in padded table slots must
    never reach the output (0 * NaN = NaN, so masking scores alone is not
    enough — the kernel has to zero V at masked positions too)."""
    B, Hq, Hkv, D, T, NP = 2, 4, 2, 16, 4, 3
    P = 8
    q = _rand((B, Hq, D))
    kp = np.asarray(_rand((P, T, Hkv, D))).copy()
    vp = np.asarray(_rand((P, T, Hkv, D))).copy()
    table = np.asarray([[0, 1, 2], [3, 4, 5]], np.int32)
    lengths = jnp.asarray([5, 9], jnp.int32)
    # poison everything past the live prefix: tail of the partial page and
    # the fully-dead pages (6, 7 stay clean as the pool's free pages)
    kp[2], vp[2] = np.nan, np.inf     # dead page of row 0
    kp[1, 1:], vp[1, 1:] = np.inf, np.nan  # masked tail of row 0's page 1
    kp[5, 1:], vp[5, 1:] = np.nan, np.nan  # masked tail of row 1's page 5
    clean = ops.paged_attention(
        jnp.asarray(q),
        jnp.asarray(np.nan_to_num(kp, nan=0.0, posinf=0.0, neginf=0.0)),
        jnp.asarray(np.nan_to_num(vp, nan=0.0, posinf=0.0, neginf=0.0)),
        jnp.asarray(table), lengths, impl=impl,
    )
    got = ops.paged_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(table), lengths, impl=impl,
    )
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(clean), atol=1e-6, rtol=1e-6
    )


# --------------------------------------------------------------------------- #
# MoE router
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize(
    "T,E,K,C,bt",
    [(512, 16, 2, 80, 128), (256, 8, 1, 64, 256), (512, 64, 8, 72, 64),
     (256, 128, 2, 8, 128)],
)
def test_moe_router_vs_oracle(T, E, K, C, bt):
    logits = _rand((T, E))
    ge, gs, gw, gk = ops.moe_router(
        logits, k=K, capacity=C, impl="pallas", block_t=bt
    )
    re_, rs_, rw_, rk_ = ref.route_topk(logits, k=K, capacity=C)
    np.testing.assert_array_equal(np.asarray(ge), np.asarray(re_))
    np.testing.assert_array_equal(np.asarray(gs), np.asarray(rs_))
    np.testing.assert_array_equal(np.asarray(gk), np.asarray(rk_))
    np.testing.assert_allclose(np.asarray(gw), np.asarray(rw_), atol=1e-6)


# --------------------------------------------------------------------------- #
# scans
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize(
    "B,S,Di,N,bd,bs",
    [(2, 128, 256, 16, 128, 32), (1, 64, 512, 16, 512, 64),
     (2, 96, 128, 8, 64, 32)],
)
def test_selective_scan_vs_oracle(B, S, Di, N, bd, bs):
    x = _rand((B, S, Di))
    dt = jnp.asarray(RNG.uniform(1e-3, 1e-1, size=(B, S, Di)), jnp.float32)
    a = -jnp.asarray(RNG.uniform(0.5, 2.0, size=(Di, N)), jnp.float32)
    b = _rand((B, S, N))
    c = _rand((B, S, N))
    d = _rand((Di,))
    got = ops.selective_scan(x, dt, a, b, c, d, impl="pallas",
                             block_d=bd, block_s=bs)
    want = ref.selective_scan(x, dt, a, b, c, d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize(
    "B,S,D,dtype",
    [(2, 128, 256, jnp.float32), (1, 64, 512, jnp.float32),
     (2, 128, 256, jnp.bfloat16)],
)
def test_gated_linear_scan_vs_oracle(B, S, D, dtype):
    a = jnp.asarray(RNG.uniform(0.1, 0.99, size=(B, S, D)), dtype)
    b = _rand((B, S, D), dtype)
    got = ops.gated_linear_scan(a, b, impl="pallas", block_d=128, block_s=32)
    want = ref.gated_linear_scan(a, b)
    atol = 3e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=atol, rtol=atol,
    )


# --------------------------------------------------------------------------- #
# dispatch/combine roundtrip
# --------------------------------------------------------------------------- #
def test_moe_dispatch_combine_conservation():
    T, E, K, D = 128, 8, 2, 32
    logits = _rand((T, E))
    tokens = _rand((T, D))
    e, s, w, keep = ref.route_topk(logits, k=K, capacity=T)  # no drops
    buf = ref.moe_dispatch(tokens, e, s, keep, n_experts=E, capacity=T)
    out = ref.moe_combine(buf, e, s, w, keep)
    # identity experts + weights summing to 1 -> combine(dispatch(x)) == x
    np.testing.assert_allclose(np.asarray(out), np.asarray(tokens),
                               atol=1e-5, rtol=1e-5)


# --------------------------------------------------------------------------- #
# chunked (associative) scans — the §Perf iteration variants
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("chunk", [16, 32, 128])
def test_selective_scan_chunked_vs_oracle(chunk):
    B, S, Di, N = 2, 100, 64, 8
    x = _rand((B, S, Di))
    dt = jnp.asarray(RNG.uniform(1e-3, 0.3, size=(B, S, Di)), jnp.float32)
    a = -jnp.asarray(RNG.uniform(0.5, 8.0, size=(Di, N)), jnp.float32)
    b = _rand((B, S, N))
    c = _rand((B, S, N))
    d = _rand((Di,))
    got = ref.selective_scan_chunked(x, dt, a, b, c, d, chunk=chunk)
    want = ref.selective_scan(x, dt, a, b, c, d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("chunk", [16, 64])
def test_gated_linear_scan_chunked_vs_oracle(chunk):
    B, S, D = 2, 90, 48
    a = jnp.asarray(RNG.uniform(0.05, 0.99, size=(B, S, D)), jnp.float32)
    b = _rand((B, S, D))
    got = ref.gated_linear_scan_chunked(a, b, chunk=chunk)
    want = ref.gated_linear_scan(a, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5, rtol=3e-5)


def test_chunked_scan_gradients_match():
    """The perf variant must be a drop-in for training (same gradients)."""
    B, S, Di, N = 1, 64, 32, 4
    x = _rand((B, S, Di))
    dt = jnp.asarray(RNG.uniform(1e-3, 0.2, size=(B, S, Di)), jnp.float32)
    a = -jnp.asarray(RNG.uniform(0.5, 4.0, size=(Di, N)), jnp.float32)
    b = _rand((B, S, N))
    c = _rand((B, S, N))
    d = _rand((Di,))

    g1 = jax.grad(lambda xx: (ref.selective_scan(xx, dt, a, b, c, d) ** 2).sum())(x)
    g2 = jax.grad(
        lambda xx: (ref.selective_scan_chunked(xx, dt, a, b, c, d, chunk=16) ** 2).sum()
    )(x)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=5e-4,
                               rtol=5e-4)


# --------------------------------------------------------------------------- #
# flash attention BACKWARD kernels (custom VJP) vs jax.grad of the oracle
# --------------------------------------------------------------------------- #
FA_BWD_CASES = [
    (1, 2, 1, 128, 64, True, None),
    (2, 4, 2, 128, 64, True, None),
    (1, 2, 2, 128, 64, False, None),
    (1, 4, 1, 128, 64, True, 64),
]


@pytest.mark.parametrize("case", FA_BWD_CASES, ids=[str(c) for c in FA_BWD_CASES])
def test_flash_attention_backward_vs_oracle(case):
    from repro.kernels.flash_attention_bwd import flash_attention_vjp

    B, Hq, Hkv, S, D, causal, window = case
    q = _rand((B, Hq, S, D))
    k = _rand((B, Hkv, S, D))
    v = _rand((B, Hkv, S, D))

    def loss_kernel(q, k, v):
        return (flash_attention_vjp(q, k, v, causal, window, None, 64,
                                    64) ** 2).sum()

    def loss_ref(q, k, v):
        return (ref.attention(q, k, v, causal=causal, window=window) ** 2).sum()

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_flash_attention_lse_output():
    from repro.kernels.flash_attention import flash_attention

    q = _rand((1, 2, 128, 64))
    k = _rand((1, 2, 128, 64))
    v = _rand((1, 2, 128, 64))
    out, lse = flash_attention(q, k, v, causal=True, return_lse=True)
    # lse == logsumexp of the masked scores
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / (64 ** 0.5)
    mask = jnp.tril(jnp.ones((128, 128), bool))
    s = jnp.where(mask[None, None], s, -1e30)
    want = jax.nn.logsumexp(s, axis=-1)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want),
                               atol=2e-4, rtol=2e-4)
