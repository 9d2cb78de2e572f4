"""Layer shapes of a configuration file's ``model`` section, for the cost
functions of ``bench/kernels`` and ``bench/flops.py``."""
from __future__ import annotations

from typing import List, Tuple


def layers(config: dict, kind: str) -> List[Tuple[dict, int]]:
    """(block spec, invocations per token) for every block whose mixer is of
    ``kind`` (e.g. ``mamba2``, ``mla``); ``attention`` names ``full``, the
    GQA attention that the attention kernels' cost files price."""
    kind = "full" if kind == "attention" else kind
    return [(b, reps) for b, reps in blocks(config)
            if b["mixer"]["kind"] == kind]


def blocks(config: dict) -> List[Tuple[dict, int]]:
    m = config["model"]
    return [(m["blocks"][name], int(g["repeats"]))
            for g in m["groups"] for name in g["blocks"]]


def next_pow2(n: int) -> int:
    v = 1
    while v < n:
        v *= 2
    return v
