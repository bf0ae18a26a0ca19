"""The harness, run on the CPU past its look for a chip, with the timed
path sound and then broken underneath: ``correct`` must come out true
for the sound path and false for each fault the cell can have.

qwen3-4b.chat (one chip, a served model): a decode step that returns
the KV pool unchanged, and a token altered where it is produced.  It has
no exchange between chips, and no batch mean to take.  The control, the
float32 reference computed with fp8 matmul inputs, put in the program's
place, comes out not correct by the harness's own rule.  A server whose
admission or decode bypasses the benchmark's hooks stops the run.
"""
import io
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_cells import tiny_chat_cell
from bench import control, run

SEED = 2**31 + 5
# long enough, on a loaded test machine, to finish the 16 tokens the
# small cell's check needs
WINDOW_S = 4.0


def _run(cell):
    return run.run(cell, SEED, WINDOW_S, False, jax.devices()[:1],
                   t_start=time.monotonic(), out=io.StringIO())


def test_chat_sound_path_is_correct():
    res = _run(tiny_chat_cell())
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"out_tok_s", "itl_p95_ms", "setup_s"}


def test_chat_step_that_leaves_the_pool_unchanged(monkeypatch):
    from repro.launch import serve

    build = serve._paged_decode_views_fn

    def broken(model, ctx, layout):
        step = build(model, ctx, layout)

        def keep_pool(params, token, positions, views, tables):
            before = jax.tree_util.tree_map(jnp.copy, views)
            logits, _ = step(params, token, positions, views, tables)
            return logits, before

        return keep_pool

    monkeypatch.setattr(serve, "_paged_decode_views_fn", broken)
    res = _run(tiny_chat_cell())
    assert not res["correct"], res["checks"]
    assert res["checks"]["logit_gap"]["value"] > res["checks"]["logit_gap"][
        "limit"]


def test_chat_token_altered_where_produced(monkeypatch):
    from repro.launch import serve

    decode = serve.PagedServer._decode_via_tables

    def altered(self, tables):
        logits = decode(self, tables).copy()
        live = [i for i, r in enumerate(self.active) if r is not None]
        i = live[0]
        j = int(np.argmax(logits[i]))
        logits[i, (j + 1) % logits.shape[1]] = logits[i, j] + 1
        return logits

    monkeypatch.setattr(serve.PagedServer, "_decode_via_tables", altered)
    res = _run(tiny_chat_cell())
    assert not res["correct"], res["checks"]


def test_chat_control_fails_the_limit():
    cell = tiny_chat_cell()
    r = control.served_readings(run, cell, SEED, WINDOW_S,
                                jax.devices()[:1])
    limit = cell["settings"]["logit_gap_limit"]
    assert r["tokens_short"] == 0, r
    assert r["program_correct"] and r["program"] <= limit, r
    assert not r["control_correct"] and r["control"] > limit, r


def test_chat_admission_that_bypasses_the_hook(monkeypatch):
    from repro.launch import serve

    prefill = serve.PagedServer._prefill_pages

    def admit(self):
        # admission by another path: the prefill is not the wrapped one
        self._prefill_pages = lambda req: prefill(self, req)
        return admit_orig(self)

    admit_orig = serve.PagedServer._admit
    monkeypatch.setattr(serve.PagedServer, "_admit", admit)
    with pytest.raises(RuntimeError, match="hooks missed"):
        _run(tiny_chat_cell())


def test_chat_server_without_table_width(monkeypatch):
    from repro.launch import serve

    init = serve.PagedServer.__init__

    def renamed(self, *a, **k):
        init(self, *a, **k)
        self._width = self.__dict__.pop("_table_width")

    monkeypatch.setattr(serve.PagedServer, "__init__", renamed)
    with pytest.raises(RuntimeError, match="_table_width"):
        _run(tiny_chat_cell())
