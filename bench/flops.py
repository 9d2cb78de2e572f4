"""Model FLOPs of the served work, for the step's share of the chip's peak.

Counted for useful tokens only (no bucket or batch padding, no empty decode
slot): 2 FLOPs per matmul weight per token, each layer kind's FLOPs per
token that do not depend on the context (a recurrence, a conv) and per
causal query-key pair, from the kind's file ``bench/layers/<part>.<kind>.py``
(a kind with no file raises ``LookupError``), and the output head for each
token whose logits are taken.
"""
from __future__ import annotations

from bench import layers, shapes


def _parts(config: dict):
    """(spec, layer module, invocations per token) of every block's mixer
    and FFN; an FFN of kind ``none`` has none."""
    out = []
    for block, reps in shapes.blocks(config):
        for part in ("mixer", "ffn"):
            spec = block[part]
            if spec["kind"] != "none":
                out.append((spec, layers.module(part, spec["kind"]), reps))
    return out


def matmul_params(config: dict) -> int:
    """Matmul weights a token passes through, embedding and head excluded
    (a shared block counts at every invocation)."""
    d = int(config["model"]["d_model"])
    return sum(reps * mod.matmul_params(spec, d)
               for spec, mod, reps in _parts(config))


def _pair_flops(config: dict, phase: str) -> float:
    """FLOPs per causal query-key pair, summed over layers."""
    return sum(reps * mod.pair_flops(spec, phase)
               for spec, mod, reps in _parts(config)
               if hasattr(mod, "pair_flops"))


def _token_flops(config: dict) -> float:
    """FLOPs per token that do not depend on the context."""
    return 2.0 * matmul_params(config) + sum(
        reps * mod.state_flops(spec) for spec, mod, reps in _parts(config)
        if hasattr(mod, "state_flops"))


def head(config: dict) -> float:
    m = config["model"]
    return 2.0 * m["d_model"] * m["vocab_size"]


def prefill(config: dict, length: int, start: int = 0,
            with_head: bool = True) -> float:
    """Prompt tokens [start, length), each attending to every earlier token
    and itself, and the head where the prompt ends."""
    n = length - start
    if n <= 0:
        return 0.0
    pairs = n * start + n * (n + 1) / 2
    return (n * _token_flops(config) + pairs * _pair_flops(config, "prefill")
            + (head(config) if with_head else 0.0))


def decode_block(config: dict, lengths, block: int) -> float:
    """``block`` tokens for each active slot, slot ``i`` starting with
    ``lengths[i]`` tokens cached."""
    tok, pair, hd = (_token_flops(config), _pair_flops(config, "decode"),
                     head(config))
    n = len(lengths) * block
    keys = sum(block * (int(L) + 1) + block * (block - 1) / 2
               for L in lengths)
    return n * (tok + hd) + keys * pair
