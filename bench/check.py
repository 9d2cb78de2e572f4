"""Whether what the timed path served is correct.

After the window has closed and the program's state is freed, a sample of
the window's finished requests, drawn from the seed, is run through the
plain reference (``reference.py``) once, teacher-forced on each prompt and
the tokens the program served.  The number compared is the widest gap by
which a served token's reference logit lies below the reference's best
logit at that position (greedy decoding serves the argmax, so a correct
server's gaps are rounding only).  The control reads, at the same
positions, the gap of the token that the float8 reference puts first.

The sample always holds the longest finished request and, where the window
had both, one request of each route; the rest are drawn at random, up to
``SAMPLE`` requests (some hundreds of served tokens).
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

SAMPLE = 4
PAD = 512                   # reference sequence length is a multiple of this


def pick_sample(done: List[dict], seed: int, k: int = SAMPLE) -> List[dict]:
    """``done``: finished window requests, each with ``rid``, ``route``,
    ``prompt``, ``tokens``.  Deterministic in ``seed``."""
    if not done:
        return []
    rng = np.random.default_rng([seed, 2])
    longest = max(done, key=lambda r: (len(r["prompt"]) + len(r["tokens"]),
                                       r["rid"]))
    chosen = [longest]
    for route in sorted({r["route"] for r in done}):
        if route != longest["route"]:
            pool = [r for r in done if r["route"] == route]
            chosen.append(pool[int(rng.integers(len(pool)))])
    taken = {r["rid"] for r in chosen}
    rest = [r for r in done if r["rid"] not in taken]
    for i in rng.permutation(len(rest))[:max(0, k - len(chosen))]:
        chosen.append(rest[int(i)])
    return chosen


def batch(sample: List[dict]):
    """(tokens (B, T), read (B, P), served (B, P), valid (B, P))."""
    T = max(len(r["prompt"]) + len(r["tokens"]) for r in sample)
    T = -(-T // PAD) * PAD
    P = max(len(r["tokens"]) for r in sample)
    B = len(sample)
    tokens = np.zeros((B, T), np.int32)
    read = np.zeros((B, P), np.int32)
    served = np.zeros((B, P), np.int32)
    valid = np.zeros((B, P), bool)
    for b, r in enumerate(sample):
        L, n = len(r["prompt"]), len(r["tokens"])
        seq = np.concatenate([r["prompt"], r["tokens"]]).astype(np.int32)
        tokens[b, :len(seq)] = seq
        read[b, :n] = L - 1 + np.arange(n)      # logits that chose token j
        served[b, :n] = r["tokens"]
        valid[b, :n] = True
    return tokens, read, served, valid


def gaps(model: dict, params, sample: List[dict], *, control: bool = False
         ) -> Dict[str, float]:
    """Widest gap of the served tokens under the reference, and with
    ``control`` also the control's widest gap."""
    import jax.numpy as jnp

    from bench import reference

    tokens, read, served, valid = batch(sample)
    ref = reference.logits(model, params, jnp.asarray(tokens),
                           jnp.asarray(read))
    best = np.asarray(ref.max(-1))
    got = np.asarray(jnp.take_along_axis(ref, jnp.asarray(served)[..., None],
                                         -1)[..., 0])
    gap = (best - got)[valid]
    out = {"logit_gap": float(gap.max()),
           "tokens_compared": int(valid.sum()),
           "mean_gap": float(gap.mean()),
           "flip_share": float((gap > 0).mean())}
    if control:
        low = reference.logits(model, params, jnp.asarray(tokens),
                               jnp.asarray(read), low=True)
        pick = low.argmax(-1)
        del low
        alt = np.asarray(jnp.take_along_axis(ref, pick[..., None], -1)[..., 0])
        cg = (best - alt)[valid]
        out["control_gap"] = float(cg.max())
        out["control_mean_gap"] = float(cg.mean())
        out["control_flip_share"] = float((cg > 0).mean())
    return out


def verdict(readings: Dict[str, float], limit: Optional[float],
            unfinished: int) -> tuple:
    """(correct, compared) where compared maps each number to its value and
    limit."""
    gap = readings.get("logit_gap")
    compared = {
        "logit_gap": {"value": gap if gap is not None and gap < float("inf")
                      else None, "limit": limit},
        "unfinished": {"value": unfinished, "limit": 0},
    }
    ok = (limit is not None and compared["logit_gap"]["value"] is not None
          and compared["logit_gap"]["value"] <= limit
          and unfinished == 0
          and readings.get("tokens_compared", 0) > 0)
    return bool(ok), compared


def control_verdict(readings: Dict[str, float], limit: Optional[float],
                    unfinished: int) -> tuple:
    """``verdict`` with the control's widest gap in place of the program's:
    the control has to come out as not correct."""
    return verdict({**readings, "logit_gap": readings["control_gap"]},
                   limit, unfinished)
