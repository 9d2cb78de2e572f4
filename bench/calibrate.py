"""Readings that the correctness limit of a configuration is set from.

    python3 bench/calibrate.py --workload <cell> --seconds 12 \\
        --seeds 101 102 ... --control-seeds 101 102 103

Runs the cell once per seed, each in a process of its own with a short
window at the cell's own load, and prints one JSON line per seed: the widest logit gap of
the served tokens under the float32 reference (the number ``correct``
compares) and, for the control seeds, the widest gap of the tokens the
float8 reference puts first at the same positions, with the verdict that
``bench/check.py`` gives that gap in the program's place
(``control_correct``, which has to be false).  The limit in the
configuration file lies between the largest program reading and the
smallest control reading (PERF.md gives both).
"""
from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--one", action="store_true",
                    help="run the first seed in this process")
    args = ap.parse_args(argv)
    if not args.one:
        # one process per seed: a process's device memory is not handed
        # back between runs, and this parent never touches the chip
        for seed in args.seeds:
            cmd = [sys.executable, __file__, "--one", "--workload",
                   args.workload, "--seconds", str(args.seconds),
                   "--seeds", str(seed)]
            if seed in args.control_seeds:
                cmd += ["--control-seeds", str(seed)]
            subprocess.run(cmd, check=False)
        return

    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench.run import run_cell
    from bench.spec import load_cell

    from bench import check

    cell = load_cell(args.workload)
    seed = args.seeds[0]
    t = time.perf_counter()
    result, compared, readings = run_cell(
        cell, seed, args.seconds, False, t_start=t,
        control=seed in args.control_seeds)
    if "control_gap" in readings:
        readings["control_correct"], _ = check.control_verdict(
            readings, compared["logit_gap"]["limit"],
            compared["unfinished"]["value"])
    print("calibration: " + json.dumps({
        "workload": cell.name, "seed": seed, **readings,
        "correct": result["correct"],
        "attempted": result["attempted"], "failed": result["failed"],
        "metrics": {k: v["value"] for k, v in result["metrics"].items()},
        "memory_peak_bytes": result["device"]["memory_peak_bytes"]}),
        flush=True)


if __name__ == "__main__":
    main()
