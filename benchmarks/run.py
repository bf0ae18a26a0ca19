"""Benchmark harness — one section per paper-evaluation axis.

The 2014 paper defers quantitative tables to its companion FPGA'13 paper
[4], whose evaluation axes are: (a) remote-access latency, (b) put
bandwidth vs message size, (c) collective performance, and (d) application
kernels.  Each axis maps to a section here; the dry-run/roofline tables in
EXPERIMENTS.md cover the at-scale story these CPU microbenches cannot.

Prints ``name,us_per_call,derived`` CSV.  Each section runs as its own
subprocess, one after another, and this process never touches JAX, so a
child can hold the chip.  With ``JAX_PLATFORMS=cpu`` each child is given
the host devices its section needs; otherwise the children see the
devices this process was given.
"""
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")


def _sub(module: str, cpu_devices: int) -> None:
    env = dict(os.environ)
    if env.get("JAX_PLATFORMS") == "cpu":
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={cpu_devices}"
        )
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", module)],
        capture_output=True, text=True, cwd=ROOT, timeout=3600, env=env,
    )
    ok = proc.returncode == 0
    for line in proc.stdout.splitlines():
        if "," in line and not line.startswith(("W", "I", "E")):
            print(line)
    if not ok:
        print(f"{module},ERROR,rc={proc.returncode}")
        sys.stderr.write(proc.stderr[-2000:] + "\n")


def main() -> None:
    print("name,us_per_call,derived")
    # (a)+(b)+(c): GASNet microbench lineage — AM latency, put bandwidth,
    # ring vs native collectives, compressed rings (8 nodes)
    _sub("gas_microbench.py", cpu_devices=8)
    # (d) compute kernels: oracle timings + Pallas parity (1 device)
    _sub("kernel_bench.py", cpu_devices=1)
    # end-to-end: train-step throughput + serving decode (1 device)
    _sub("train_serve_bench.py", cpu_devices=4)  # 4: disaggregated serve


if __name__ == "__main__":
    main()
