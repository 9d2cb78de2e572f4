"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Steps, in order: refuse anything but a TPU; enable the compile cache; make
the weights on the device from the seed; build the program's
``CrossDCDeployment`` from the cell's configuration; warm the cell's own
shapes (the engines' ``warmup`` / ``warmup_block``, then one request per
distinct prompt length of the run's traffic); serve the traffic open-loop
for a short warm-up span; measure for ``--seconds``; serve on until every
request due in the window has finished (at most ``POST_S`` more); read the
device's peak memory; free the program; compare a sample of the served
tokens with the plain float32 reference; print one JSON line.

With ``--trace 0`` the line's metrics are the cell's end-to-end metrics;
with ``--trace 1`` the profiler records the end of the window, from the
due time of the last request due ``TRACE_S`` or more before its end, and
the line carries the per-layer metrics, the device's busy time and a
breakdown; host-clock metrics then read the window before the profiler
started.  Every number compared for ``correct`` is printed
beside its limit, last on standard error and last in the line.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

WARM_S = 5.0        # open-loop traffic before the window, unmeasured
POST_S = 60.0       # the longest the window's requests are waited for
TRACE_S = 4.0       # least traced part of the window (--trace 1)
TRACE_DIR = ROOT / ".bench_trace"


class NoChip(SystemExit):
    pass


def log(msg: str):
    print(msg, flush=True)


def _pct(values, q):
    import numpy as np
    return float(np.percentile(values, q)) if len(values) else None


def end_to_end(rec, seconds: float, setup_s: float) -> dict:
    """The cell's end-to-end numbers from the driver's stamps.  A window
    request that got no first token (or never finished) counts from its due
    time to the end of serving."""
    w0, w1 = rec.window
    reqs = [q for q in rec.reqs.values() if q.segment == "window"]
    end = max([w1] + [q.finish or 0 for q in rec.reqs.values()]
              + [q.first or 0 for q in rec.reqs.values()])
    ttft = [((q.first if q.first is not None else end) - q.due) * 1e3
            for q in reqs]
    tpot = []
    for q in reqs:
        if q.first is None:
            continue
        stop = q.finish if q.finish is not None else end
        tpot.append((stop - q.first) / max(1, q.n_out - 1) * 1e3)
    tokens = sum(n for t, n in rec.token_events if w0 <= t < w1)
    return {"ttft_p90_ms": _pct(ttft, 90), "tpot_p90_ms": _pct(tpot, 90),
            "output_tok_s": tokens / seconds, "setup_s": setup_s,
            "ttft_p50_ms": _pct(ttft, 50), "tpot_p50_ms": _pct(tpot, 50)}


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, warm_s: float = WARM_S,
             post_s: float = POST_S, trace_s: float = TRACE_S,
             t_start: float = T_START, control: bool = False,
             compile_cache: bool = True):
    """One run of ``cell`` (a ``bench.spec.Cell``).  Returns the result
    line, the compared numbers and the raw readings (with ``control``, the
    float8 control's gap on the same sample too)."""
    import jax
    import numpy as np

    from bench import check, traffic
    from bench.context import Context, kernel_namer
    from bench.driver import CompileLog, OpenLoop, warm_pass
    from bench.model import build_deployment
    from bench.peaks import peaks
    from bench.spec import read_metrics
    from bench.weights import make_params
    from repro.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise NoChip(f"bench: needs a TPU; JAX found {dev.platform!r}")
    if len(devices) < cell.chips:
        raise NoChip(f"bench: cell {cell.name} needs {cell.chips} chips; "
                     f"JAX found {len(devices)}")
    peak = peaks(dev.device_kind) if require_tpu else None
    if compile_cache:
        enable_compile_cache()
        # every program, the small per-length ones of the served path too,
        # is read back from the cache after a checkout's first run
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compile_log = CompileLog()
    config = cell.config
    vocab = int(config["model"]["vocab_size"])

    t = time.perf_counter()
    params = make_params(config, seed)
    log(f"weights: seed={seed} leaves={len(jax.tree.leaves(params))} "
        f"seconds={time.perf_counter() - t:.3f}")
    dep = build_deployment(config, params)
    arrivals = traffic.generate(cell.mix, cell.rate, seconds, warm_s,
                                post_s, seed)
    prompts = traffic.prompt_tokens(seed, arrivals, vocab)

    t, c0 = time.perf_counter(), len(compile_log.log)
    serving = config["serving"]
    threshold = int(serving["threshold"])
    local = sorted({a.prompt_len for a in arrivals
                    if a.prompt_len <= threshold})
    offload = sorted({a.prompt_len for a in arrivals
                      if a.prompt_len > threshold})
    batches = [1 << i for i in range(
        int(serving.get("max_prefill_batch", 8)).bit_length())]
    if local:
        dep.pd_prefill.warmup(batches, local)
    if offload:
        dep.prfaas.warmup([1], [max(offload)])
    for dec in dep.decoders.values():
        dec.warmup_block()
    n_warm = warm_pass(dep, arrivals, seed, vocab)
    log(f"warmup: engines_and_lengths_s={time.perf_counter() - t:.3f} "
        f"distinct_prompt_lengths={n_warm} "
        f"backend_compiles={len(compile_log.log) - c0} compile_s="
        f"{sum(s for _, s in compile_log.log[c0:]):.3f}")

    driver = OpenLoop(dep, arrivals, prompts, compile_log)
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    rec = driver.run(warm_s, seconds, post_s,
                     trace_dir=str(TRACE_DIR) if trace else None,
                     trace_s=trace_s)
    setup_s = rec.window[0] - t_start
    stats = dev.memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))

    window = [q for q in rec.reqs.values() if q.segment == "window"]
    late = np.array([q.submit - q.due for q in rec.reqs.values()
                     if q.submit is not None]) * 1e3
    in_window = [(t, s) for t, s in rec.compiles
                 if rec.window[0] <= t < rec.window[1]]
    routes = {}
    for q in window:
        routes[q.route] = routes.get(q.route, 0) + 1
    log(f"generator_lateness_ms: p50={_pct(late, 50):.3f} "
        f"p90={_pct(late, 90):.3f} max={late.max():.3f} "
        f"submitted={len(late)}")
    log(f"window: requests={len(window)} routes={routes} ticks={rec.ticks} "
        f"in_window_backend_compiles={len(in_window)} in_window_compile_s="
        f"{sum(s for _, s in in_window):.3f}")

    unfinished = sum(1 for q in window if q.finish is None)
    bad = sum(1 for q in window if q.finish is not None
              and (q.truncated or len(q.tokens) != q.n_out))
    ctx_trace = None
    if trace:
        from bench import trace as trace_mod
        path = trace_mod.find(str(TRACE_DIR))
        if path is not None:
            raw = trace_mod.load(path)
            ctx_trace = trace_mod.reduce(raw, _compiles_on_trace(rec, raw),
                                         kernel_namer())
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    ctx = Context(config, rec, ctx_trace, peak)

    # free the program before the reference runs: a process's peak never
    # falls again, and the reference must not set it
    done = [{"rid": q.rid, "route": q.route, "prompt": prompts[q.rid],
             "tokens": np.asarray(q.tokens, np.int32)}
            for q in window if q.finish is not None and not q.truncated
            and len(q.tokens) == q.n_out]
    del driver, dep
    gc.collect()

    sample = check.pick_sample(done, seed)
    t = time.perf_counter()
    readings = (check.gaps(config["model"], params, sample, control=control)
                if sample else {"logit_gap": None, "tokens_compared": 0})
    log(f"reference: requests={len(sample)} routes="
        f"{[r['route'] for r in sample]} tokens_compared="
        f"{readings['tokens_compared']} seconds="
        f"{time.perf_counter() - t:.3f}")
    correct, compared = check.verdict(
        readings, config["check"]["logit_gap_limit"], unfinished)

    if trace:
        metrics = read_metrics(cell.per_layer, ctx)
    else:
        e2e = end_to_end(rec, seconds, setup_s)
        log("end_to_end_detail: " + json.dumps(e2e))
        metrics = {m["name"]: {"value": float(e2e[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end if e2e.get(m["name"]) is not None}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": len(window),
              "failed": unfinished + bad, "metrics": metrics,
              "device": device}
    if trace:
        tw = rec.trace_window
        device["busy_s"] = ctx_trace["busy_s"] if ctx_trace else 0.0
        device["window_s"] = (tw[1] - tw[0]) if tw and tw[1] else 0.0
        if ctx_trace:
            from bench import trace as trace_mod
            result["breakdown"] = {
                "device_ops": trace_mod.top(ctx_trace["ops"]),
                "idle_gaps": trace_mod.gap_breakdown(ctx_trace["gaps"])}
    result["checks"] = compared
    return result, compared, readings


def _compiles_on_trace(rec, raw):
    """Backend compiles as (start, end) on the trace's clock, placed by the
    ``bench.mark`` span that opens the trace."""
    marks = [e for e in raw["host"] if e[0] == "bench.mark"]
    if not marks or rec.trace_window is None:
        return []
    offset = marks[0][1] - rec.trace_window[0]
    return [(t - s + offset, t + offset) for t, s in rec.compiles]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # libtpu otherwise writes its logs under /tmp, outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    from bench.spec import load_cell
    cell = load_cell(args.workload)
    try:
        result, compared, _ = run_cell(cell, args.seed, args.seconds,
                                       bool(args.trace))
    except NoChip as e:
        print(str(e), file=sys.stderr, flush=True)
        return 2
    for name, c in compared.items():
        print(f"check: {name}={c['value']} limit={c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
