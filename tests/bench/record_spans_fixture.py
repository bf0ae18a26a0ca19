"""Records ``data/serve_spans.xplane.pb.gz`` on one chip: qwen3-4b at its
widths cut to 2 layers, batch 4, served by the benchmark's paged-server
driver at 16 requests/s for 0.25 s with the profiler on.  The pool is
synced to the host just before the window, so its first tick uploads the
pool again.  The trace holds the benchmark's host spans and the
program's own ``serve.*`` spans.

    python3 tests/bench/record_spans_fixture.py
"""
import gzip
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import bench_cells  # puts the checkout and src on the path
from bench import run, trace

OUT = os.path.join(HERE, "data", "serve_spans.xplane.pb.gz")
SEED = 2**31 + 13


def main() -> None:
    cell = run.load_cell("qwen3-4b.chat", bench_cells.ROOT)
    cell["config"]["num_hidden_layers"] = 2
    cell["settings"].update(batch=4, rate_rps=16.0)
    devs = run.devices(1)
    run.enable_compile_cache()
    driver = run.driver_of(cell["config"])
    state = driver.setup(cell, SEED, devs)
    state.srv._sync_host()

    reduce = trace.reduce_file

    def keep(path, n_devices):
        with open(path, "rb") as src, gzip.open(OUT, "wb") as dst:
            shutil.copyfileobj(src, dst)
        return reduce(path, n_devices)

    trace.reduce_file = keep
    rec = driver.window(state, 0.25, True)
    print(f"{OUT}: {os.path.getsize(OUT)} B; "
          f"{len(rec.host['prefills'])} admissions, "
          f"{len(rec.host['ticks'])} ticks, busy {rec.trace.busy_s:.4f} of "
          f"{rec.trace.window_s:.4f} s")

if __name__ == "__main__":
    main()
