"""One general open-loop traffic generator, driven by a mix file.

A mix file (``bench/traffic/<name>.json``) holds parameters only:

    {"arrivals": {"process": "poisson"}                       # or
     "arrivals": {"process": "mmpp", "burst_factor": 3, "period_s": 10},
     "prompt": {"median": 512, "sigma": 1.0, "min": 64, "max": 3584},
     "output": {"median": 128, "sigma": 0.7, "min": 16, "max": 480},
     "max_total": 4095}

Lengths follow lognormals truncated to ``[min, max]`` (the program's
``core.workload.LogNormalLengths`` semantics, copied here so the yardstick
does not move with the program).  ``output`` counts every token a request
returns, the prefill's first token included.

Every seed gets the same work in another order: the window holds a fixed
multiset of (prompt, output) length pairs (stratified quantiles of the two
distributions, paired by a fixed permutation) arriving after a fixed
multiset of inter-arrival gaps (stratified exponential quantiles in the
arrival process's operational time).  The seed orders the pairs and the
gaps, and draws the prompt token ids (and, elsewhere, the weights).  So
the count, the sizes and the arrival statistics of a window are the same
for every seed, and which request meets which load is not.

The timeline has three segments: ``warm_s`` of traffic before the window
(unmeasured), the window of ``window_s`` and ``post_s`` of traffic after it
(so requests due late in the window are served under the same load).  The
two outer segments reuse the window's length pairs in other orders.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np

PAIRING_SEED = 0x5EED          # fixed: which prompt quantile meets which output
ORDER_SEED = 0x0DE5            # with the run's seed: the order of requests and gaps
_STD = NormalDist()


@dataclass(frozen=True)
class Arrival:
    rid: int
    due_s: float               # seconds from the start of the timeline
    prompt_len: int
    n_out: int                 # tokens returned, the first (prefill) one included
    segment: str               # "warm" | "window" | "post"


def load_mix(path) -> dict:
    with open(path) as f:
        mix = json.load(f)
    for key in ("arrivals", "prompt", "output", "max_total"):
        if key not in mix:
            raise ValueError(f"{path}: traffic mix lacks {key!r}")
    return mix


def truncated_lognormal_quantiles(median: float, sigma: float, lo: float,
                                  hi: float, n: int) -> np.ndarray:
    """The n stratified quantiles (i + 1/2) / n of a lognormal with the given
    median and sigma, truncated to [lo, hi]."""
    mu = math.log(median)
    c_lo = _STD.cdf((math.log(lo) - mu) / sigma)
    c_hi = _STD.cdf((math.log(hi) - mu) / sigma)
    u = c_lo + (np.arange(n) + 0.5) / n * (c_hi - c_lo)
    z = np.array([_STD.inv_cdf(float(x)) for x in u])
    return np.clip(np.exp(mu + sigma * z), lo, hi)


def length_pairs(mix: dict, n: int) -> np.ndarray:
    """(n, 2) int array of (prompt_len, n_out), the same for every seed."""
    p, o = mix["prompt"], mix["output"]
    prompts = np.rint(truncated_lognormal_quantiles(
        p["median"], p["sigma"], p["min"], p["max"], n)).astype(np.int64)
    outs = np.rint(truncated_lognormal_quantiles(
        o["median"], o["sigma"], o["min"], o["max"], n)).astype(np.int64)
    outs = outs[np.random.default_rng(PAIRING_SEED).permutation(n)]
    outs = np.minimum(outs, int(mix["max_total"]) - prompts)
    if (outs < 2).any():
        raise ValueError("max_total leaves a request fewer than 2 tokens")
    return np.stack([prompts, outs], axis=1)


def rate_at(arrivals: dict, base_rate: float, t: np.ndarray) -> np.ndarray:
    """Offered rate at times ``t``.  ``mmpp`` is the mean-preserving
    square-wave two-state modulation of ``core.workload.mmpp_rate``."""
    kind = arrivals["process"]
    if kind == "poisson":
        return np.full_like(t, base_rate, dtype=np.float64)
    if kind != "mmpp":
        raise ValueError(f"unknown arrival process {kind!r}")
    bf, period = float(arrivals["burst_factor"]), float(arrivals["period_s"])
    if bf <= 1.0 or period <= 0.0:
        return np.full_like(t, base_rate, dtype=np.float64)
    duty, low = (0.5, 2.0 - bf) if bf <= 2.0 else (1.0 / bf, 0.0)
    high = np.mod(t, period) < duty * period
    return base_rate * np.where(high, bf, low)


def _due_times(arrivals, rate, t0, t1, n, rng) -> np.ndarray:
    """n arrival times in [t0, t1): a fixed multiset of exponential gaps in
    operational time (cumulative offered rate), ordered by ``rng``."""
    if n == 0:
        return np.zeros(0)
    grid = np.arange(t0, t1 + 1e-3, 1e-3)
    lam = np.concatenate([[0.0], np.cumsum(rate_at(arrivals, rate,
                                                   grid[:-1]) * 1e-3)])
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps = rng.permutation(gaps)
    pos = (np.cumsum(gaps) - gaps[0]) / gaps.sum() * lam[-1]
    return np.interp(pos, lam, grid)


def generate(mix: dict, rate: float, window_s: float, warm_s: float,
             post_s: float, seed: int) -> List[Arrival]:
    """The run's whole timeline, sorted by due time: the same requests and
    gaps for every seed, in the seed's order.  Window requests are due in
    [warm_s, warm_s + window_s)."""
    n_win = max(1, int(round(_offered(mix, rate, warm_s, warm_s + window_s))))
    pairs = length_pairs(mix, n_win)
    rng = np.random.default_rng([ORDER_SEED, seed])
    out: List[Arrival] = []
    spans = (("warm", 0.0, warm_s),
             ("window", warm_s, warm_s + window_s),
             ("post", warm_s + window_s, warm_s + window_s + post_s))
    for seg, t0, t1 in spans:
        n = (n_win if seg == "window"
             else int(round(_offered(mix, rate, t0, t1))))
        if n == 0 or t1 <= t0:
            continue
        due = _due_times(mix["arrivals"], rate, t0, t1, n, rng)
        order = rng.permutation(n_win)
        idx = np.concatenate([order] * (n // n_win + 1))[:n]
        for t, i in zip(due, idx):
            out.append(Arrival(0, float(t), int(pairs[i, 0]),
                               int(pairs[i, 1]), seg))
    out.sort(key=lambda a: a.due_s)
    return [Arrival(k, a.due_s, a.prompt_len, a.n_out, a.segment)
            for k, a in enumerate(out)]


def _offered(mix: dict, rate: float, t0: float, t1: float) -> float:
    grid = np.arange(t0, t1, 1e-3)
    return float(rate_at(mix["arrivals"], rate, grid).sum() * 1e-3)


def prompt_tokens(seed: int, arrivals: List[Arrival], vocab: int):
    """Random, unshared prompt token ids per request, from the seed."""
    rng = np.random.default_rng([seed, 1])
    return {a.rid: rng.integers(0, vocab, a.prompt_len).astype(np.int32)
            for a in arrivals}
