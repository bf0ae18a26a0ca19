"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json`` at the root of the
checkout: the cell's configuration (``bench/configs/<file>``, whose
``driver`` names the module under ``bench/drivers/`` that drives the
system under test), its traffic mix (``bench/traffic/<traffic>.json``),
the cell's own settings (``bench/cells/<cell>.json``) and one reader per
metric (``bench/metrics/<metric>.py``).  A new cell, mix, configuration
or metric is a new file and a new entry; no file here changes.

One run is one process.  It loads and warms up every shape the cell's
traffic uses (set-up), measures for ``--seconds``, checks what the timed
path produced against the plain reference, and prints one JSON line as
the last line of its standard output.  It fails, with no result line,
when JAX finds no TPU or fewer chips than the cell needs.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import sys
import time

T_START = time.monotonic()

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def fail(msg: str) -> None:
    print(f"bench: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.isfile(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> dict:
    """Everything one cell is made of, by name."""
    spec = read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r}; cells: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    entry = configs[cell["config"]]
    bench = os.path.join(root, "bench")
    return {
        "name": name,
        "chips": int(cell["chips"]),
        "config": read_json(os.path.join(root, entry["file"])),
        "mix": read_json(os.path.join(bench, "traffic",
                                      cell["traffic"] + ".json")),
        "settings": read_json(os.path.join(bench, "cells", name + ".json")),
        "metrics": metrics_of(spec, name),
    }


def metrics_of(spec: dict, cell: str) -> dict:
    """{"end_to_end": [...], "per_layer": [...]} metric entries this
    cell reports: those with no ``workloads`` key, or that list it."""
    return {
        kind: [m for m in spec[kind]
               if "workloads" not in m or cell in m["workloads"]]
        for kind in ("end_to_end", "per_layer")
    }


def driver_of(config: dict):
    return load_module(
        os.path.join(BENCH_DIR, "drivers", config["driver"] + ".py"),
        "bench_driver_" + config["driver"])


def read_metrics(entries, record) -> dict:
    out = {}
    for m in entries:
        reader = load_module(
            os.path.join(BENCH_DIR, "metrics", m["name"] + ".py"),
            "bench_metric_" + m["name"].replace(".", "_"))
        value = reader.read(record)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def devices(chips: int):
    """The chips this cell runs on; fails without a TPU or enough chips."""
    import jax

    found = jax.devices()
    if found[0].platform != "tpu":
        fail(f"no TPU: JAX found {found[0].platform} devices")
    if len(found) < chips:
        fail(f"the cell needs {chips} chips, JAX found {len(found)}")
    return found[:chips]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache``: a
    fixed path inside the checkout, so that only a cell's first run in a
    checkout compiles and two checkouts share nothing."""
    import jax

    path = os.path.join(ROOT, ".jax_cache")
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def memory_peak(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


def verdict(checks: dict, failed: int) -> bool:
    """``correct``: every number compared is within its limit, and no
    request failed."""
    return bool(checks) and failed == 0 and all(
        c["value"] <= c["limit"] for c in checks.values())


def run(cell: dict, seed: int, seconds: float, trace: bool, devs,
        t_start: float = T_START, out=sys.stdout) -> dict:
    """Set up, measure, check and report one run; returns the result."""
    driver = driver_of(cell["config"])
    state = driver.setup(cell, seed, devs)
    setup_s = time.monotonic() - t_start
    record = driver.window(state, seconds, trace)
    record.setup_s = setup_s
    kind = "per_layer" if trace else "end_to_end"
    metrics = read_metrics(cell["metrics"][kind], record)
    device = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "memory_peak_bytes": memory_peak(devs),
    }
    if trace:
        device["busy_s"] = record.trace.busy_s
        device["window_s"] = record.trace.window_s
    result = {
        "correct": False,
        "attempted": int(record.attempted),
        "failed": int(record.failed),
        "metrics": metrics,
        "device": device,
    }
    if trace:
        result["breakdown"] = record.trace.breakdown()
    # the check runs after the program's state is freed and the peak read
    checks = driver.check(state, record)
    del state
    gc.collect()
    result["correct"] = verdict(checks, record.failed)
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), file=out, flush=True)
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        fail(f"no system under test at {src}")
    sys.path.insert(0, src)
    sys.path.insert(0, ROOT)
    try:
        cell = load_cell(args.workload)
    except (KeyError, OSError, ValueError) as e:
        fail(f"cannot load cell {args.workload!r}: {e}")
    devs = devices(cell["chips"])
    enable_compile_cache()
    run(cell, args.seed, args.seconds, bool(args.trace), devs)


if __name__ == "__main__":
    main()
