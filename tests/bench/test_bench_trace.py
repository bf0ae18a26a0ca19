"""The trace reduction against a small trace recorded on one v5e chip
(``data/serve_small.xplane.pb.gz``: qwen3-4b widths cut to 2 layers,
batch 4, served by the benchmark's paged-server driver for 0.15 s with
``--trace 1``), and the compile counter."""
import gzip
import os
import shutil

import jax
import jax.numpy as jnp
import pytest

from bench import compiles, trace

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "serve_small.xplane.pb.gz")


@pytest.fixture(scope="module")
def reduction(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "serve_small.xplane.pb"
    with gzip.open(FIXTURE) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return trace.reduce_file(str(path), n_devices=1)


def test_window_and_busy_time(reduction):
    r = reduction
    assert set(r.devices) == {0}
    assert 0 < r.busy_s < r.window_s
    busy, inside = r.busy_in("step")
    assert 0 < busy <= r.busy_s + 1e-9 and busy <= inside <= r.window_s


def test_programs_and_host_spans(reduction):
    r = reduction
    decodes = r.host_count("decode")
    runs, secs = r.module_stats(lambda n: n == "jit_step")
    assert decodes > 0 and runs == decodes and secs > 0
    runs, secs = r.module_stats(lambda n: n == "jit__lambda")
    assert runs == r.host_count("admission") > 0 and secs > 0
    assert r.host_count("step") >= decodes


def test_kernel_time_is_inside_the_decode_program(reduction):
    r = reduction
    kernel = r.op_seconds(lambda n: n.startswith("%paged_attention_decode"))
    _, decode = r.module_stats(lambda n: n == "jit_step")
    assert 0 < kernel < decode
    # a while op encloses its body's ops: it is left out, not counted twice
    assert r.op_seconds(lambda n: n.startswith("%while")) == 0


def test_breakdown(reduction):
    b = reduction.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert all(name in trace.HOST_SPANS + ("none",)
               for name, _ in b["idle_gaps"])


def test_op_and_module_names():
    assert trace.op_name("%fusion.85 = bf16[2]{0} fusion(%p)") == "%fusion.85"
    assert trace.module_name("jit_step(1766126577)") == "jit_step"


def test_compile_counter_counts_only_inside():
    f = jax.jit(lambda x: x * 3 + 1)
    x = jnp.ones((7, 5)).block_until_ready()
    with compiles.Counter() as c:
        f(x).block_until_ready()
        f(x).block_until_ready()
    assert c.count == 1
    with compiles.Counter() as c:
        f(x).block_until_ready()
    assert c.count == 0


def _reader(name):
    from bench import run

    return run.load_module(
        os.path.join(run.BENCH_DIR, "metrics", name + ".py"),
        "test_reader_" + name)


def _record(**kw):
    from bench.record import Record

    return Record(config={}, settings={}, device_kind="TPU v5 lite",
                  n_chips=1, **kw)


@pytest.mark.parametrize("name", ["decode_step_ms", "prefill_ms_per_req"])
def test_program_reader_raises_when_its_program_is_renamed(reduction, name,
                                                           monkeypatch):
    reader = _reader(name)
    assert reader.read(_record(trace=reduction)) > 0
    assert reader.read(_record()) is None  # an untraced run
    monkeypatch.setattr(reader, "PROGRAM", "jit_renamed")
    with pytest.raises(RuntimeError, match="jit_renamed"):
        reader.read(_record(trace=reduction))


def test_batch_and_pool_occupancy_readers():
    rec = _record(host={"decode_lengths": [[5, 9], [6, 10, 300], [7]],
                        "pool_pages": [40, 57, 52]})
    assert _reader("decode_slots_live").read(rec) == 2.0
    assert _reader("pool_pages_peak").read(rec) == 57
    assert _reader("decode_slots_live").read(_record()) is None
    assert _reader("pool_pages_peak").read(_record()) is None
