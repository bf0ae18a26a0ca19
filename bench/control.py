"""Readings that set a cell's correctness limit: the program's number on
several seeds, and the control's, one precision step below the
configuration's (see PERF.md, "How correct is decided").

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 30

For a served model, each seed makes its weights and serves a short
window of the cell's own traffic at the cell's own load.  The driver's
own ``check`` then judges the served tokens, and, in the program's
place, the tokens that an fp8 reference (fp8 e4m3 matmul inputs) puts
first at the same positions: the control.  Each comes out ``correct``
or not by the harness's own rule (``run.verdict``); the control must
not.

The benchmark's own runs never run this.  It prints one JSON line per
seed.  It needs the chips the cell asks for.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
LOWP = "float8_e4m3fn"


def served_readings(run, cell, seed, seconds, devs) -> dict:
    driver = run.driver_of(cell["config"])
    state = driver.setup(cell, seed, devs)
    rec = driver.window(state, seconds, False)
    # frees the program's state before the reference runs
    program, control = driver.check(state, rec, lowp=LOWP)
    return {"seed": seed,
            "program": program["logit_gap"]["value"],
            "control": control["logit_gap"]["value"],
            "program_correct": run.verdict(program, rec.failed),
            "control_correct": run.verdict(control, rec.failed),
            "tokens_short": program["tokens_short"]["value"],
            "window_s": rec.window_s}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, ROOT)
    from bench import run

    cell = run.load_cell(args.workload)
    devs = run.devices(cell["chips"])
    run.enable_compile_cache()
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.monotonic()
        out = served_readings(run, cell, seed, args.seconds, devs)
        out["s"] = time.monotonic() - t0
        print(json.dumps(out), flush=True)
        gc.collect()


if __name__ == "__main__":
    main()
