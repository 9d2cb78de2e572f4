"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only table6] [--smoke]

``--smoke`` shortens simulator horizons so the whole harness finishes in
seconds (CI / tier-1 verify); full runs reproduce the paper-scale numbers.
Prints ``name,us_per_call,derived`` CSV rows (benchmarks/common.emit).
"""
import argparse
import inspect
import sys
import time
import traceback

from benchmarks import (common, engine_bench, fig5_gridsearch, kernel_bench,
                        scenario_grid, serve_live, sim_ttft,
                        table3_kv_throughput, table5_profile,
                        table6_deployment)
from repro.compile_cache import enable_compile_cache

MODULES = {
    "table3": table3_kv_throughput,    # Table 3 / Figure 2 (Φ_kv by model)
    "table5": table5_profile,          # Table 5 (1T hybrid profile)
    "table6": table6_deployment,       # Table 6 (deployment comparison)
    "fig5": fig5_gridsearch,           # Figure 5 (grid search slices)
    "sim": sim_ttft,                   # §4.3 TTFT/egress via simulator
    "grid": scenario_grid,             # burst x skew x fluct x topology grid
    "kernels": kernel_bench,           # micro-bench + machine calibration
    "engine": engine_bench,            # serving hot path (decode/admit/buckets)
    "serve": serve_live,               # live launcher + policy/actual x-val
}


def _call_main(mod, smoke: bool):
    main = mod.main
    if smoke and "smoke" in inspect.signature(main).parameters:
        return main(smoke=True)
    return main()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=list(MODULES))
    ap.add_argument("--smoke", action="store_true",
                    help="short horizons; finish the harness in seconds")
    args = ap.parse_args()
    enable_compile_cache()
    names = [args.only] if args.only else list(MODULES)
    print("name,us_per_call,derived")
    failed = []
    for name in names:
        print(f"# === {name} ===", flush=True)
        t0 = time.time()
        common.reset_clock()       # per-module bench_wall_s in artifacts
        try:
            _call_main(MODULES[name], args.smoke)
        except Exception:
            failed.append(name)
            traceback.print_exc()
        print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
    if failed:
        print(f"# FAILED: {failed}")
        sys.exit(1)
    print("# all benchmarks complete")


if __name__ == "__main__":
    main()
