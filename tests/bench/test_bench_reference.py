"""The plain references against the program's own path, at small sizes on
the CPU: the float32 Qwen3 reference against the model's forward with
the same seeded weights."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_cells import tiny_chat_cell
from bench import weights
from bench.drivers import paged_server
from bench.reference import qwen3


def _f32_cfg():
    cfg = tiny_chat_cell()["config"]
    cfg["torch_dtype"] = "float32"
    return cfg


def test_stacked_weights_equal_single_layers():
    from repro.models.build import build_model
    from repro.parallel.ctx import RunCtx

    cfg = tiny_chat_cell()["config"]
    model = build_model(paged_server.arch_config(cfg))
    struct, _ = model.abstract_init(RunCtx(mesh=None, remat="none"))
    params = weights.make_params(struct, 2**33 + 5)
    key = weights.key_from_seed(2**33 + 5)
    wq = params["dec"][0]["b0_global"]["attn"]["wq"]
    assert wq.dtype == jnp.bfloat16
    for layer in range(wq.shape[0]):
        one = weights.make(key, "attn.wq", wq.shape[1:], jnp.float32, layer)
        np.testing.assert_array_equal(np.asarray(wq[layer], np.float32),
                                      np.asarray(one))
    tok = params["io"]["tok"]
    np.testing.assert_array_equal(
        np.asarray(tok, np.float32),
        np.asarray(weights.make(key, "tok", tok.shape, jnp.float32)))
    assert weights.key_from_seed(1) is not None
    with pytest.raises(ValueError):
        weights.key_from_seed(-1)


def test_qwen3_reference_matches_the_model_in_float32():
    from repro.models.build import build_model
    from repro.parallel.ctx import RunCtx

    cfg, seed = _f32_cfg(), 7
    model = build_model(paged_server.arch_config(cfg))
    ctx = RunCtx(mesh=None, remat="none")
    struct, _ = model.abstract_init(ctx)
    params = weights.make_params(struct, seed)
    tokens = np.random.default_rng(0).integers(0, 512, (2, 24))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(model.train_logits(params, ctx, {
            "inputs": jnp.asarray(tokens, jnp.int32)}), np.float32)
    h = qwen3.hidden(cfg, seed, tokens, np.arange(2 * 24))
    tok = weights.make(weights.key_from_seed(seed), "tok", (512, 128),
                       jnp.float32)
    got = np.asarray(h @ tok.T).reshape(2, 24, 512)
    np.testing.assert_allclose(got, want, rtol=2e-4,
                               atol=2e-4 * np.abs(want).max())


def test_gaps_of_the_reference_own_tokens_are_zero():
    cfg, seed = _f32_cfg(), 11
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, 512, 20).tolist()
    # greedy tokens of the reference itself: every gap is zero
    out = []
    for _ in range(3):
        seq = np.asarray([prompt + out])
        h = qwen3.hidden(cfg, seed, seq, np.asarray([seq.shape[1] - 1]))
        tok = weights.make(weights.key_from_seed(seed), "tok", (512, 128),
                           jnp.float32)
        out.append(int(np.argmax(np.asarray(h @ tok.T)[0])))
    g = qwen3.gaps(cfg, seed, [(prompt, out)], cache_len=32, n_seqs=2)
    assert g.shape == (1, 3)
    np.testing.assert_allclose(g, 0.0, atol=1e-6)
    wrong = [(out[0] + 1) % 512] + out[1:]
    assert qwen3.gaps(cfg, seed, [(prompt, wrong)], 32, 2)[0, 0] > 0
