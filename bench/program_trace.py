"""The program's own spans in a profiler trace, and the device's idle gaps
named by them.

The serving path marks its phases with ``repro.serving.spans`` spans, named
``prfaas.*``; each opens a ``TraceAnnotation``, so the trace's host plane
holds them beside the benchmark's ``bench.*`` spans, on the trace's clock.
``bench.trace.load`` keeps only the ``bench.*`` ones; ``load`` here reads
the ``prfaas.*`` ones, and ``program_gaps`` names the idle intervals of
``bench.trace.reduce``'s ``gaps``, in the same order, by the innermost
``prfaas.*`` span that holds each interval's midpoint, or else by the label
``gaps`` gave it.  So the idle inside the driver's one ``bench.decode_block``
span splits into the decode block's host phases (``prfaas.decode.dispatch``,
``.sync``, ``.bookkeep``).
"""
from __future__ import annotations

from typing import List, Tuple

from bench import trace

PREFIX = "prfaas."


def load(path: str) -> List[Tuple[str, float, float]]:
    """Host events named ``prfaas.*`` as (name, start s, end s) from the
    trace's origin, the clock of ``bench.trace.load``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    return [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for plane in pd.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events
            if e.name.startswith(PREFIX)]


def program_gaps(raw: dict, program, reduced: dict):
    """``reduced["gaps"]`` (of ``bench.trace.reduce(raw, ...)``) relabelled:
    each idle interval by the innermost ``prfaas.*`` span of ``program``
    that holds its midpoint, else by its label there.  The spans come from
    one host thread, so they nest: the innermost holding a point is the
    open one that started last."""
    devices, host = raw["devices"], raw["host"]
    if not devices or not reduced["gaps"]:
        return []
    all_t = [t for d in devices for _, a, b in d["ops"] for t in (a, b)]
    all_t += [t for _, a, b in host for t in (a, b)]
    idle = trace.idle_intervals([(x, y) for _, x, y in devices[0]["ops"]],
                                min(all_t), max(all_t))
    if len(idle) != len(reduced["gaps"]):
        raise ValueError("the reduction's gaps are not this trace's")
    mids = sorted((0.5 * (a + b), i) for i, (a, b) in enumerate(idle))
    spans = sorted(program, key=lambda e: (e[1], -e[2]))
    names = [label for label, _ in reduced["gaps"]]
    stack, j = [], 0
    for mid, i in mids:
        while j < len(spans) and spans[j][1] <= mid:
            stack.append(spans[j])
            j += 1
        while stack and stack[-1][2] < mid:
            stack.pop()
        if stack:
            names[i] = stack[-1][0]
    return [(name, s) for name, (_, s) in zip(names, reduced["gaps"])]
