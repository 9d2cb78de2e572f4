"""Layer shapes of a configuration file's ``model`` section, for the cost
functions of ``bench/kernels`` and ``bench/flops.py``."""
from __future__ import annotations

from typing import List, Tuple


def layers(config: dict, kind: str) -> List[Tuple[dict, int]]:
    """(mixer spec, invocations per token) for every block whose mixer is
    ``attention`` or the linear ``kind`` (e.g. ``mamba2``)."""
    m = config["model"]
    out = []
    for g in m["groups"]:
        for name in g["blocks"]:
            b = m["blocks"][name]
            mixer = b["mixer"]
            if (kind == "attention" and mixer["type"] == "attention") or \
                    (mixer["type"] == "linear" and mixer["kind"] == kind):
                out.append((b, int(g["repeats"])))
    return out


def blocks(config: dict) -> List[Tuple[dict, int]]:
    m = config["model"]
    return [(m["blocks"][name], int(g["repeats"]))
            for g in m["groups"] for name in g["blocks"]]


def next_pow2(n: int) -> int:
    v = 1
    while v < n:
        v *= 2
    return v
