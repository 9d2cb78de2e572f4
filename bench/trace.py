"""Reduction of a profiler trace to what the per-layer metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``.  Its
device planes (``/device:TPU:<n>``) carry one line of XLA operations (each
named by its HLO instruction text; a Pallas kernel is one
``tpu_custom_call`` with no kernel name) and one line of whole programs (``XLA Modules``, named ``jit_<function>(...)``); the host
plane (``/host:CPU``) carries the driver's ``bench.*`` annotations.  All
times share one origin.  ``reduce`` returns:

* ``busy_s``: the union of operation intervals on each device, averaged
  over the devices (idle share = 1 - busy / window);
* ``ops``: device seconds per operation, named by its HLO instruction with
  the number dropped (``%fusion.12 = ...`` -> ``fusion``); a Pallas kernel
  (a ``tpu_custom_call``, which the trace does not name) by the kernel
  whose operand signature it matches (``bench/kernels/<kernel>.py``
  ``match``), else ``pallas``; loops and conditionals, which contain other
  operations, are left out;
* ``modules``: device seconds and count per program name;
* ``gaps``: the device's idle intervals, each named by the innermost
  ``bench.*`` host span (or the compile) it falls in;
* ``waiting_s``: the device's idle time inside ``bench.wait_arrival``
  spans, where the host had no request to serve and waited for the next.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Callable, Dict, List, Optional, Tuple

OPS_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
CONTAINERS = ("while", "conditional", "call")
_SUFFIX = re.compile(r"\.\d+$")
_MODULE = re.compile(r"^(jit_[A-Za-z0-9_]+)")
_CUSTOM = re.compile(r"^%\S+ = (.*?) custom-call\((.*)\), custom_call_target="
                     r"\"tpu_custom_call\"")
_LAYOUT = re.compile(r"\{[^}]*\}")


def find(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def op_name(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion``."""
    return _SUFFIX.sub("", name.split(" = ")[0].lstrip("%"))


def signature(text: str) -> Optional[str]:
    """A Pallas call's types, ``out <- operand operand ...`` with layouts
    dropped (``bf16[128,1,64] <- s32[4] bf16[128,1,64] ...``); None for
    any other operation."""
    m = _CUSTOM.match(text)
    if m is None:
        return None
    out = _LAYOUT.sub("", m.group(1))
    args = [_LAYOUT.sub("", a.strip().split(" ")[0])
            for a in re.split(r", (?=\(?[a-z0-9]+\[)",
                              _LAYOUT.sub("", m.group(2)))]
    return out + " <- " + " ".join(args)


def module_name(name: str) -> str:
    m = _MODULE.match(name)
    return m.group(1) if m else op_name(name.split("(")[0])


def union_length(intervals: List[Tuple[float, float]]) -> float:
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def idle_intervals(intervals, lo: float, hi: float):
    gaps, end = [], lo
    for a, b in sorted(intervals):
        if a > end:
            gaps.append((end, min(a, hi)))
        end = max(end, b)
    if end < hi:
        gaps.append((end, hi))
    return [(a, b) for a, b in gaps if b > a]


def overlap_length(a, b) -> float:
    """Total overlap of two lists of disjoint intervals."""
    a, b = sorted(a), sorted(b)
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _events(line):
    return [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events]


def load(path: str) -> dict:
    """Device operations and programs, and host ``bench.*`` spans, in
    seconds from the trace's origin."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU") or \
                plane.name.startswith("/device:GPU"):
            ops, mods = [], []
            for line in plane.lines:
                if line.name in OPS_LINES:
                    ops += _events(line)
                elif line.name in MODULE_LINES:
                    mods += _events(line)
            if ops or mods:
                devices.append({"name": plane.name, "ops": ops,
                                "modules": mods})
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host += [e for e in _events(line)
                         if e[0].startswith("bench.")]
    return {"devices": devices, "host": host}


def reduce(raw: dict, compiles: List[Tuple[float, float]] = (),
           kernel_of: Callable[[str], Optional[str]] = lambda sig: None
           ) -> dict:
    """``compiles``: (start, end) of backend compiles on the trace's clock;
    ``kernel_of`` names a Pallas call from its signature."""
    devices, host = raw["devices"], raw["host"]
    all_t = [t for d in devices for _, a, b in d["ops"] for t in (a, b)]
    all_t += [t for _, a, b in host for t in (a, b)]
    if not all_t:
        return {"busy_s": 0.0, "span_s": 0.0, "ops": {}, "modules": {},
                "gaps": [], "waiting_s": 0.0, "devices": 0}
    lo, hi = min(all_t), max(all_t)
    busy = [union_length([(a, b) for _, a, b in d["ops"]]) for d in devices]
    ops: Dict[str, float] = defaultdict(float)
    modules: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0])
    names: Dict[str, str] = {}
    for d in devices:
        for text, a, b in d["ops"]:
            if text not in names:
                sig = signature(text)
                names[text] = (op_name(text) if sig is None
                               else kernel_of(sig) or "pallas")
            if names[text] not in CONTAINERS:
                ops[names[text]] += b - a
        for name, a, b in d["modules"]:
            m = modules[module_name(name)]
            m[0] += b - a
            m[1] += 1
    gaps, waiting = [], 0.0
    if devices:
        spans = sorted(host, key=lambda e: e[2] - e[1])
        idle = idle_intervals([(x, y) for _, x, y in devices[0]["ops"]],
                              lo, hi)
        waiting = overlap_length(idle, [(x, y) for name, x, y in host
                                        if name == "bench.wait_arrival"])
        for a, b in idle:
            mid = 0.5 * (a + b)
            label = "host.other"
            if any(c0 <= mid <= c1 for c0, c1 in compiles):
                label = "compile"
            else:
                for name, s0, s1 in spans:
                    if s0 <= mid <= s1:
                        label = name
                        break
            gaps.append((label, b - a))
    return {"busy_s": sum(busy) / max(1, len(busy)), "span_s": hi - lo,
            "ops": dict(ops), "modules": {k: tuple(v)
                                          for k, v in modules.items()},
            "gaps": gaps, "waiting_s": waiting, "devices": len(devices)}


def top(items: Dict[str, float], n: int = 10):
    return [[k, v] for k, v in sorted(items.items(), key=lambda kv: -kv[1])[:n]]


def gap_breakdown(gaps, n: int = 10):
    by: Dict[str, float] = defaultdict(float)
    for label, s in gaps:
        by[label] += s
    return top(by, n)
