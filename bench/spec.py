"""Finds everything a cell needs by name, from files alone.

``BENCHMARK.json`` names each cell's configuration and traffic mix; the
files behind those names are

    bench/configs/<config>.json      sizes, serving settings, check limit
    bench/traffic/<traffic>.json     the mix's parameters (bench/traffic.py)
    bench/cells/<cell>.json          the cell's offered rate (req/s)
    bench/metrics/<metric>.py        one reader per per-layer metric
    bench/layers/<part>.<kind>.py    one mixer or FFN kind's reference
                                     equations and FLOPs (bench/layers)

so a later change adds a cell, a configuration, a mix, a metric or a layer
kind by adding files, never by editing one.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclass
class Cell:
    name: str
    config: dict
    traffic_name: str
    mix: dict
    rate: float
    chips: int
    end_to_end: List[dict]
    per_layer: List[dict]


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT, bench: Path = BENCH,
              benchmark: Optional[dict] = None) -> Cell:
    spec = benchmark if benchmark is not None else load_benchmark(root)
    by_name = {w["name"]: w for w in spec["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(by_name)})")
    w = by_name[name]
    config = load_json(bench / "configs" / f"{w['config']}.json")
    mix = load_json(bench / "traffic" / f"{w['traffic']}.json")
    cell = load_json(bench / "cells" / f"{name}.json")
    return Cell(name=name, config=config, traffic_name=w["traffic"], mix=mix,
                rate=float(cell["rate_per_s"]), chips=int(w["chips"]),
                end_to_end=[m for m in spec["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in spec["per_layer"]
                           if _applies(m, name)])


def metric_reader(name: str, bench: Path = BENCH) -> Callable:
    """``read(ctx)`` of ``bench/metrics/<name>.py``: returns the metric's
    value, or None where the run holds nothing for it to read."""
    path = bench / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def read_metrics(metrics: List[dict], ctx, bench: Path = BENCH) -> Dict:
    out = {}
    for m in metrics:
        value = metric_reader(m["name"], bench)(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
