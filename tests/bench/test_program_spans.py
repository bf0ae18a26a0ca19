"""The paged server's own spans and counters, on the CPU at the small
cell's widths: every ``serve.*`` span lands in the profiler's trace,
nested in its tick; nothing is recorded while neither the profiler nor
a ring tracer runs; the program's counters equal what the benchmark's
hooks record on the same run; and the trace reduction, with the
program's spans added to the host spans it keeps, gives every existing
reader the same value and puts the device's idle gaps down to them."""
import glob
import gzip
import os
import shutil
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from bench_cells import read, tiny_chat_cell
from bench import run, trace
from bench.record import Record

SEED = 2**31 + 11
SPANS = ("serve.tick", "serve.admit", "serve.prefill", "serve.flatten",
         "serve.pool_write", "serve.grow", "serve.tables", "serve.upload",
         "serve.patch", "serve.decode", "serve.logits", "serve.advance",
         "serve.sync_host")
DATA = os.path.join(os.path.dirname(__file__), "data")
FIXTURES = ("serve_small.xplane.pb.gz", "serve_spans.xplane.pb.gz")


def _driver(cell):
    return run.driver_of(cell["config"])


def _record(cell):
    rec = Record(config=cell["config"], settings=cell["settings"],
                 device_kind="cpu", n_chips=1)
    rec.host.update(prefills=[], decode_lengths=[], pool_pages=[])
    return rec


def _host_spans(trace_dir):
    """(name, start_ns, end_ns) of every ``serve.*`` event on the host
    planes of the one trace under ``trace_dir``."""
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
    return out


def _pressed_server(cell):
    """The small cell's server with a pool of 12 pages for four rows of
    up to eight: lazy growth runs the pool dry, so ticks preempt (a host
    sync), resume and upload the pool again.  Replays are priced cheap,
    so the scheduler recomputes: a resume prefills again."""
    from repro.launch.serve import PagedServer

    cfg, settings = cell["config"], cell["settings"]
    driver = _driver(cell)
    model_srv, params = driver.build_server(cfg, settings, SEED)
    srv = PagedServer(
        model_srv.model, model_srv.ctx, params, int(settings["batch"]),
        int(settings["cache_len"]), page_tokens=int(settings["page_tokens"]),
        n_pool_pages=12, decode_step_us=1e-3, prefill_us=1e-3)
    return srv, driver


def _submit(srv, vocab, n=6, shared=32, tail=16, max_new=40):
    from repro.launch.serve import Request

    rng = np.random.default_rng(SEED)
    system = rng.integers(0, vocab, shared).tolist()
    for rid in range(n):
        srv.submit(Request(
            rid=rid, prompt=system + rng.integers(0, vocab, tail).tolist(),
            max_new=max_new))


def test_spans_land_in_the_profiler_trace_inside_their_tick(tmp_path):
    cell = tiny_chat_cell()
    srv, driver = _pressed_server(cell)
    _submit(srv, int(cell["config"]["vocab_size"]))
    rec = _record(cell)
    driver._hook(srv, rec, [time.monotonic()])
    steps = 0
    with jax.profiler.trace(str(tmp_path)):
        while srv._pending() and steps < 400:
            srv.step()
            steps += 1
    assert not srv._pending()
    spans = _host_spans(tmp_path)
    count = {name: sum(1 for s, _, _ in spans if s == name)
             for name in SPANS}
    assert all(count.values()), count
    m = srv.metrics.snapshot()
    assert count["serve.tick"] == steps
    # one admission span a prefill: first admissions and recompute resumes
    assert count["serve.admit"] == m["admissions"] == len(
        rec.host["prefills"])
    assert m["admissions"] > 6  # some request was recomputed
    assert count["serve.decode"] == count["serve.logits"] == m["ticks"] \
        == len(rec.host["decode_lengths"])
    assert count["serve.sync_host"] == m["host_syncs"]
    ticks = [(a, b) for s, a, b in spans if s == "serve.tick"]
    for name, a, b in spans:
        assert any(t0 <= a and b <= t1 for t0, t1 in ticks), name


def test_nothing_is_recorded_without_the_profiler_or_a_tracer(tmp_path):
    from repro.obs import trace as obs_trace

    cell = tiny_chat_cell()
    srv, _ = _pressed_server(cell)
    _submit(srv, int(cell["config"]["vocab_size"]), n=2, max_new=10)
    assert not obs_trace.active().enabled
    with obs_trace.span("serve.tick") as sp:
        assert sp is None
    for _ in range(3):
        srv.step()
    # a session opened afterwards holds none of the spans made before it
    with jax.profiler.trace(str(tmp_path)):
        pass
    assert _host_spans(tmp_path) == []
    # a ring tracer records the same spans while it is enabled, no longer
    tr = obs_trace.enable(capacity=1024)
    try:
        srv.step()
    finally:
        obs_trace.disable()
    n = len(tr.events)
    names = {e.name for e in tr.events}
    assert {"serve.tick", "serve.decode", "serve.logits"} <= names
    (tick,) = tr.spans(name="serve.tick")
    assert tick.args["live"] > 0
    srv.step()
    assert len(tr.events) == n


def test_counters_equal_the_benchmark_hooks_record():
    cell = tiny_chat_cell(rate_rps=8.0)
    driver = _driver(cell)
    state = driver.setup(cell, SEED, jax.devices()[:1])
    srv = state.srv
    srv.metrics.gauge("pool_pages_peak").set(0)
    before = srv.metrics.snapshot()
    rec = driver.window(state, 3.0, False)
    after = srv.metrics.snapshot()
    got = {k: after[k] - before[k] for k in after if k != "pool_pages_peak"}
    host = rec.host
    assert got["admissions"] == len(host["prefills"]) > 0
    assert got["ticks"] == len(host["decode_lengths"]) == len(host["ticks"])
    assert got["decode_rows"] == sum(map(len, host["decode_lengths"]))
    assert after["pool_pages_peak"] == max(host["pool_pages"])
    assert got["prefill_tokens"] == sum(n for n, _, _, _ in host["prefills"])
    assert got["prefill_cached_tokens"] == sum(
        c for _, c, _, _ in host["prefills"]) > 0
    # every admission brings its whole flattened cache to the host
    page_row = srv.layout.page_elems * 4
    assert got["d2h_bytes"] == got["admissions"] * srv.layout.n_pages \
        * page_row
    # fresh pages go up as patch chunks of whole rows
    assert got["h2d_bytes"] > 0
    assert got["host_syncs"] == 0


@pytest.fixture(scope="module", params=FIXTURES)
def reductions(request, tmp_path_factory):
    """One recorded chip trace reduced twice: with the host spans the
    benchmark keeps, and with the program's spans added to them."""
    path = tmp_path_factory.mktemp("trace") / request.param[:-3]
    with gzip.open(os.path.join(DATA, request.param)) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    plain = trace.reduce_file(str(path), n_devices=1)
    mp = pytest.MonkeyPatch()
    mp.setattr(trace, "HOST_SPANS", trace.HOST_SPANS + SPANS)
    try:
        extended = trace.reduce_file(str(path), n_devices=1)
    finally:
        mp.undo()
    return request.param, plain, extended


def _reader(name):
    return run.load_module(
        os.path.join(run.BENCH_DIR, "metrics", name + ".py"),
        "test_spans_reader_" + name.replace(".", "_"))


@pytest.mark.parametrize("name", ["decode_step_ms", "prefill_ms_per_req",
                                  "idle_share.serve",
                                  "paged_attention_decode_roofline"])
def test_existing_readers_unchanged_by_the_program_spans(reductions, name):
    _, plain, extended = reductions
    cfg = dict(read("bench/configs/qwen3-4b.json"), num_hidden_layers=2)

    def value(reduction):
        rec = Record(config=cfg, settings={"page_tokens": 16},
                     device_kind="TPU v5 lite", n_chips=1, trace=reduction,
                     host={"decode_lengths": [[40, 72, 100]] * 8})
        return _reader(name).read(rec)

    assert value(plain) is not None
    assert value(extended) == value(plain)
    for bench_span in ("admission", "decode", "patch", "step", "wait"):
        assert extended.busy_in(bench_span) == plain.busy_in(bench_span)
        assert extended.host_count(bench_span) == plain.host_count(
            bench_span)
    assert extended.busy_s == plain.busy_s
    assert extended.breakdown()["device_ops"] == plain.breakdown()[
        "device_ops"]


def test_program_spans_match_the_programs_runs(reductions):
    fixture, _, r = reductions
    if fixture != "serve_spans.xplane.pb.gz":
        assert r.host_count("serve.tick") == 0  # recorded before them
        return
    admits = r.host_count("serve.admit")
    assert admits == r.host_count("admission") > 0
    assert r.module_stats(lambda n: n == "jit__lambda")[0] == admits
    decodes = r.host_count("serve.decode")
    assert decodes == r.host_count("decode") == r.host_count("serve.logits")
    assert r.module_stats(lambda n: n == "jit_step")[0] == decodes
    assert r.host_count("serve.tick") == r.host_count("step")
    assert r.host_count("serve.upload") == 1
    # the device works inside a tick's decode and admissions, and waits
    # in its host work: each span's busy time is within its length
    for name in ("serve.tick", "serve.admit", "serve.logits"):
        busy, inside = r.busy_in(name)
        assert 0 < busy <= inside


def test_idle_gaps_are_put_down_to_the_program_spans(reductions):
    fixture, plain, extended = reductions
    if fixture != "serve_spans.xplane.pb.gz":
        return
    gaps = extended.breakdown()["idle_gaps"]
    assert gaps and all(name in trace.HOST_SPANS + SPANS
                        for name, _ in gaps)
    # no gap is left on a benchmark span that wraps the server's calls
    assert not {name for name, _ in gaps} & {"step", "decode", "admission",
                                             "patch"}
    assert [s for _, s in gaps] == [s for _, s in plain.breakdown()[
        "idle_gaps"]]
