"""Named host spans of the serving path, on the profiler's clock.

``span(name, **attrs)`` marks one phase of the serving path::

    with span("prfaas.wire", rid=r.rid) as sp:
        ...
        sp.attrs["bytes"] = nbytes
    wall = sp.seconds

Each span does three things:

* opens a ``jax.profiler.TraceAnnotation(name)``, so that in a profiler
  trace it lands on the host plane, on the trace's clock, beside the
  device planes;
* on exit appends ``(name, t0, t1, attrs)`` to a bounded per-process ring
  (``recent()``), times in ``time.perf_counter()`` seconds;
* exposes its own duration (``seconds``), so that a caller that keeps a
  wall reads it from the span.

The ring is always on; at ``MAXLEN`` spans the oldest fall out.  One
``jax.monitoring`` listener credits every backend compile to the innermost
span open when it ran (``compiles()``: which phase recompiled).  All spans
of the serving path are named ``prfaas.*``.
"""
from __future__ import annotations

import time
from collections import deque
from typing import Dict, List, Optional, Tuple

import jax

MAXLEN = 65536
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_now = time.perf_counter
_TraceAnnotation = jax.profiler.TraceAnnotation
_ring: deque = deque(maxlen=MAXLEN)
_open: List[str] = []                 # names of the open spans, innermost last
_compiles: Dict[Optional[str], List] = {}


class span:
    """Context manager for one named phase; see the module doc."""

    __slots__ = ("name", "attrs", "t0", "t1", "_annotation")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs
        self.t0 = self.t1 = None

    def __enter__(self) -> "span":
        _open.append(self.name)
        annotation = self._annotation = _TraceAnnotation(self.name)
        annotation.__enter__()
        self.t0 = _now()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        t1 = self.t1 = _now()
        self._annotation.__exit__(exc_type, exc, tb)
        _open.pop()
        _ring.append((self.name, self.t0, t1, self.attrs))
        return False

    @property
    def seconds(self) -> float:
        """The span's duration; for a span still open, so far."""
        end = self.t1 if self.t1 is not None else _now()
        return end - self.t0


def recent() -> List[Tuple[str, float, float, dict]]:
    """The finished spans still in the ring, oldest first."""
    return list(_ring)


def compiles() -> Dict[Optional[str], Tuple[int, float]]:
    """Backend compiles per innermost open span (None: outside any span):
    ``{span: (count, seconds)}``."""
    return {k: (n, s) for k, (n, s) in _compiles.items()}


def _on_event(event: str, duration: float, **_):
    if event == COMPILE_EVENT:
        entry = _compiles.setdefault(_open[-1] if _open else None, [0, 0.0])
        entry[0] += 1
        entry[1] += float(duration)


jax.monitoring.register_event_duration_secs_listener(_on_event)
