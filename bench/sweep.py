"""Knee sweep: the highest offered rate a cell's configuration sustains.

    python3 bench/sweep.py --workload <cell> --rates 1 1.5 2 2.5 3 \\
        --seconds 30 --seed 7

One process and one set-up (weights, deployment, warm-up) serve the cell's
traffic mix open-loop at each rate in turn, each on a timeline of its own
(a 5 s warm-up span, the window, then serving until the window's requests
finish).  For each rate it prints the backlog's growth across the window
(requests submitted and not yet finished, fitted linearly over the
window), the TTFT and TPOT percentiles, and the output rate.  The knee is
the highest rate whose backlog does not grow (slope under
``STEADY_PER_S``, every window request finished); the sweep stops at the
first rate whose backlog grows by more than ``OVERLOAD_PER_S``.  A cell's
rate (``bench/cells/<cell>.json``, written with ``--write-cell``) is four
fifths of the knee.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

WARM_S = 5.0
POST_S = 60.0
STEADY_PER_S = 0.05
OVERLOAD_PER_S = 0.25


def backlog_growth(rec) -> float:
    """Requests per second by which the backlog grows over the window
    (least-squares slope of submitted minus finished)."""
    import numpy as np

    w0, w1 = rec.window
    ts = np.linspace(w0, w1, 64)
    sub = np.array([q.submit for q in rec.reqs.values()
                    if q.submit is not None])
    fin = np.array([q.finish for q in rec.reqs.values()
                    if q.finish is not None])
    backlog = [(sub <= t).sum() - (fin <= t).sum() for t in ts]
    return float(np.polyfit(ts - w0, backlog, 1)[0])


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--write-cell", action="store_true",
                    help="write 4/5 of the knee as the cell's rate")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax

    from bench.spec import load_cell

    if jax.devices()[0].platform != "tpu":
        sys.exit("sweep: needs a TPU")
    points = sweep(load_cell(args.workload), args.rates, args.seconds,
                   args.seed)
    steady = [p["rate"] for p in points
              if p["backlog_growth_per_s"] < STEADY_PER_S
              and p["unfinished"] == 0]
    knee = max(steady) if steady else None
    rate = round(0.8 * knee, 3) if knee else None
    print("sweep_knee: " + json.dumps({"workload": args.workload,
                                       "knee_per_s": knee,
                                       "rate_per_s": rate}), flush=True)
    if args.write_cell and rate:
        from bench.spec import BENCH
        with open(BENCH / "cells" / f"{args.workload}.json", "w") as f:
            json.dump({"rate_per_s": rate, "knee_per_s": knee,
                       "sweep": points}, f, indent=1)
            f.write("\n")


def sweep(cell, rates, seconds: float, seed: int, post_s: float = POST_S,
          compile_cache: bool = True):
    """Serve ``cell``'s mix at each rate on one deployment; prints one
    ``sweep_point`` line per rate and returns them."""

    import jax

    from bench import traffic
    from bench.driver import CompileLog, OpenLoop, warm_pass
    from bench.model import build_deployment
    from bench.run import end_to_end
    from bench.weights import make_params
    from repro.compile_cache import enable_compile_cache

    if compile_cache:
        enable_compile_cache()
        # every program, the small per-length ones of the served path too,
        # is read back from the cache after a checkout's first run
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    config = cell.config
    vocab = int(config["model"]["vocab_size"])
    compile_log = CompileLog()
    params = make_params(config, seed)
    dep = build_deployment(config, params)
    timelines = []
    for i, rate in enumerate(rates):
        arr = traffic.generate(cell.mix, rate, seconds, WARM_S, post_s, seed)
        arr = [dataclasses.replace(a, rid=a.rid + i * 100_000) for a in arr]
        timelines.append((rate, arr))
    serving = config["serving"]
    threshold = int(serving["threshold"])
    lengths = {a.prompt_len for _, arr in timelines for a in arr}
    local = sorted(n for n in lengths if n <= threshold)
    offload = sorted(n for n in lengths if n > threshold)
    batches = [1 << i for i in range(
        int(serving.get("max_prefill_batch", 8)).bit_length())]
    t = time.perf_counter()
    if local:
        dep.pd_prefill.warmup(batches, local)
    if offload:
        dep.prfaas.warmup([1], [max(offload)])
    for dec in dep.decoders.values():
        dec.warmup_block()
    warm_pass(dep, [a for _, arr in timelines for a in arr], seed,
              vocab)
    print(f"sweep: {cell.name} setup_s={time.perf_counter() - t:.1f}",
          flush=True)
    driver, points = None, []
    for rate, arr in timelines:
        prompts = traffic.prompt_tokens(seed, arr, vocab)
        if driver is None:
            driver = OpenLoop(dep, arr, prompts, compile_log)
        else:
            driver.reset(arr, prompts)
        n0 = len(compile_log.log)
        rec = driver.run(WARM_S, seconds, post_s)
        e2e = end_to_end(rec, seconds, 0.0)
        window = [q for q in rec.reqs.values() if q.segment == "window"]
        points.append({
            "workload": cell.name, "rate": rate,
            "window_requests": len(window),
            "unfinished": sum(q.finish is None for q in window),
            "backlog_growth_per_s": backlog_growth(rec),
            "ttft_p50_ms": e2e["ttft_p50_ms"],
            "ttft_p90_ms": e2e["ttft_p90_ms"],
            "tpot_p50_ms": e2e["tpot_p50_ms"],
            "tpot_p90_ms": e2e["tpot_p90_ms"],
            "output_tok_s": e2e["output_tok_s"],
            "backend_compiles": len(compile_log.log) - n0})
        print("sweep_point: " + json.dumps(points[-1]), flush=True)
        if points[-1]["backlog_growth_per_s"] > OVERLOAD_PER_S:
            break
        # let the backlog of this rate drain before the next one
        scheds = list(dep.schedulers.values())
        t = time.perf_counter()
        while any(s.has_work for s in scheds) and \
                time.perf_counter() - t < 120:
            for s in scheds:
                if s.has_work:
                    s.tick()
    return points


if __name__ == "__main__":
    main()
