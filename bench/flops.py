"""Model FLOPs of the served work, for the step's share of the chip's peak.

Counted for useful tokens only (no bucket or batch padding, no empty decode
slot): 2 FLOPs per matmul weight per token, 4 * heads * head_dim per causal
query-key pair, the Mamba2 recurrence and conv (4 * heads * dk * dv and
2 * K * channels per token), and the output head for each token whose
logits are taken.
"""
from __future__ import annotations

from bench import shapes


def matmul_params(config: dict) -> int:
    """Matmul weights a token passes through, embedding and head excluded
    (a shared block counts at every invocation)."""
    d = int(config["model"]["d_model"])
    total = 0
    for block, reps in shapes.blocks(config):
        m = block["mixer"]
        if m["type"] == "attention":
            H, Hkv, D = m["q_heads"], m["kv_heads"], m["head_dim"]
            n = d * H * D * 2 + d * Hkv * D * 2
        else:
            H, dk, dv = m["heads"], m["key_dim"], m["value_dim"]
            n = d * H * (2 * dk + dv)               # q, k, v
            n += d * H + d * H * dv + H * dv * d    # decay, gate, output
        if block["ffn"]["kind"] == "dense":
            n += 3 * d * block["ffn"]["d_ff"]
        total += n * reps
    return total


def _pair_flops(config: dict) -> float:
    """FLOPs per causal query-key pair, summed over attention layers."""
    return sum(reps * 4.0 * b["mixer"]["q_heads"] * b["mixer"]["head_dim"]
               for b, reps in shapes.layers(config, "attention"))


def _token_flops(config: dict) -> float:
    """FLOPs per token that do not depend on the context."""
    f = 2.0 * matmul_params(config)
    for b, reps in shapes.layers(config, "mamba2"):
        m = b["mixer"]
        f += reps * 4.0 * m["heads"] * m["key_dim"] * m["value_dim"]
        f += reps * 2.0 * m["conv_kernel"] * m["heads"] * (
            2 * m["key_dim"] + m["value_dim"])
    return f


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
    return (n * _token_flops(config) + pairs * _pair_flops(config)
            + (head(config) if with_head else 0.0))


def decode_block(config: dict, lengths, block: int) -> float:
    """``block`` tokens for each active slot, slot ``i`` starting with
    ``lengths[i]`` tokens cached."""
    tok, pair, hd = _token_flops(config), _pair_flops(config), head(config)
    n = len(lengths) * block
    keys = sum(block * (int(L) + 1) + block * (block - 1) / 2
               for L in lengths)
    return n * (tok + hd) + keys * pair
