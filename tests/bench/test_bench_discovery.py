"""A cell, a configuration, a traffic mix and a metric join the benchmark
as new files and entries; the harness finds them by name and no file it
already has changes.  Without a TPU, or without the system under test
beside it, the command fails and prints no result."""
import json
import os
import shutil
import subprocess
import sys

from bench_cells import ROOT


def _copy_benchmark(dst):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for p in spec["paths"]:
        shutil.copytree(os.path.join(ROOT, p), os.path.join(dst, p),
                        ignore=shutil.ignore_patterns("__pycache__"))
    return spec


def test_new_files_are_discovered(tmp_path):
    spec = _copy_benchmark(tmp_path)
    before = {p: open(p, "rb").read()
              for p in (tmp_path / "bench").rglob("*.py")}
    b = tmp_path / "bench"
    cfg = json.load(open(b / "configs" / "qwen3-4b.json"))
    cfg["name"] = "new-model"
    (b / "configs" / "new-model.json").write_text(json.dumps(cfg))
    (b / "traffic" / "new-mix.json").write_text(
        (b / "traffic" / "chat.json").read_text())
    (b / "cells" / "new-model.new-mix.json").write_text(
        (b / "cells" / "qwen3-4b.chat.json").read_text())
    (b / "metrics" / "new_metric.py").write_text(
        "def read(record):\n    return record.host['tokens'] * 2\n")
    spec["configs"].append({"name": "new-model", "source": "x",
                            "file": "bench/configs/new-model.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "new-model.new-mix",
                              "config": "new-model", "traffic": "new-mix",
                              "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "new_metric", "unit": "count",
                              "better": "higher", "source": "host_clock",
                              "layer": "x", "moves": "out_tok_s",
                              "workloads": ["new-model.new-mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    sys.path.insert(0, str(tmp_path))
    try:
        import importlib.util

        s = importlib.util.spec_from_file_location("copied_run",
                                                   b / "run.py")
        run = importlib.util.module_from_spec(s)
        s.loader.exec_module(run)
        cell = run.load_cell("new-model.new-mix", root=str(tmp_path))
        assert cell["config"]["name"] == "new-model"
        assert cell["mix"]["kind"] == "open_loop"
        names = [m["name"] for m in cell["metrics"]["per_layer"]]
        assert "new_metric" in names
        assert "new_metric" not in [
            m["name"] for m in
            run.load_cell("qwen3-4b.chat",
                          root=str(tmp_path))["metrics"]["per_layer"]]

        class Rec:
            host = {"tokens": 21}

        got = run.read_metrics(
            [m for m in cell["metrics"]["per_layer"]
             if m["name"] == "new_metric"], Rec())
        assert got == {"new_metric": {"value": 42.0, "unit": "count"}}
        assert run.driver_of(cell["config"]).__name__.endswith(
            "paged_server")
    finally:
        sys.path.remove(str(tmp_path))
    after = {p: open(p, "rb").read()
             for p in (tmp_path / "bench").rglob("*.py") if p in before}
    assert after == before


def _bench(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen3-4b.chat",
         "--seed", "2147483699", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    assert proc.returncode != 0
    for line in proc.stdout.splitlines():
        assert not line.strip().startswith("{"), line


def test_fails_without_a_tpu():
    proc = _bench(ROOT)
    _no_result(proc)
    assert "no TPU" in proc.stderr


def test_fails_with_only_the_benchmark_files(tmp_path):
    _copy_benchmark(tmp_path)
    _no_result(_bench(tmp_path))
