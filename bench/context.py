"""What a metric reader is handed: the run's records, its reduced trace and
the chip's peaks, with the selections every reader needs."""
from __future__ import annotations

import importlib.util
from typing import Iterable, List, Optional

import numpy as np

from bench import peaks as peaks_mod
from bench.spec import BENCH


class Context:
    def __init__(self, config: dict, rec, trace: Optional[dict] = None,
                 peak: Optional[dict] = None):
        self.config = config
        self.rec = rec
        self.trace = trace
        self.peak = peak

    # ------------------------------------------------------------ requests
    def window_requests(self) -> List:
        return [q for q in self.rec.reqs.values() if q.segment == "window"]

    def _in_trace(self, t: Optional[float]) -> bool:
        tw = self.rec.trace_window
        return (t is not None and tw is not None and tw[1] is not None
                and tw[0] <= t < tw[1])

    def traced(self, items: Iterable[dict]) -> List[dict]:
        """Units, chunks or blocks that started inside the traced window."""
        return [x for x in items if self._in_trace(x["t0"])]

    def traced_requests(self, route: Optional[str] = None) -> List:
        """Requests whose first token (the end of their prefill, wire and
        trim) fell inside the traced window."""
        return [q for q in self.rec.reqs.values()
                if self._in_trace(q.first)
                and (route is None or q.route == route)]

    def in_host_span(self, t: Optional[float]) -> bool:
        """Inside the part of the window that host-clock readings cover:
        the whole window, or in a traced run the part before the profiler
        started (starting it stalls the host)."""
        a, b = self.rec.host_span
        return t is not None and a <= t < b

    # --------------------------------------------------------------- trace
    def device_seconds(self, op: str) -> float:
        return self.trace["ops"].get(op, 0.0) if self.trace else 0.0

    def module_seconds(self, names: Iterable[str]) -> float:
        if not self.trace:
            return 0.0
        return sum(s for m, (s, _) in self.trace["modules"].items()
                   if m in names)

    def roofline(self, kernel: str) -> Optional[float]:
        """Least time at the chip's peaks for the kernel's traced calls over
        its device time, in %; None where the trace holds no such call."""
        mod = kernel_module(kernel)
        seconds = self.device_seconds(kernel)
        if not self.peak or seconds <= 0:
            return None
        flops, nbytes = mod.cost(self)
        if flops <= 0 and nbytes <= 0:
            return None
        return 100.0 * peaks_mod.least_seconds(flops, nbytes,
                                               self.peak) / seconds


def kernel_module(kernel: str):
    path = BENCH / "kernels" / f"{kernel}.py"
    spec = importlib.util.spec_from_file_location("bench_kernel_" + kernel,
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def kernel_namer():
    """Names a Pallas call from its signature by the first
    ``bench/kernels/<kernel>.py`` whose ``match`` accepts it."""
    mods = {p.stem: kernel_module(p.stem)
            for p in sorted((BENCH / "kernels").glob("*.py"))
            if p.stem != "__init__"}

    def kernel_of(sig: str) -> Optional[str]:
        return next((k for k, m in mods.items() if m.match(sig)), None)

    return kernel_of


def p90(values) -> Optional[float]:
    values = [v for v in values if v is not None]
    return float(np.percentile(values, 90)) if values else None
