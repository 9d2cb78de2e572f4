"""Dense flash-decode attention (``kernels/decode_attn.py``).

Per decode step and attention layer, each active slot with L keys (its
cached context plus the new token): FLOPs 4 * H * D * L; bytes the live
k and v rows, 2 * Hkv * D * L in bf16, plus q and o.  Bytes count live
keys only: the kernel's reads of dead capacity are what the share shows.
"""
import re

from bench import shapes

BF16 = 2


def match(sig: str) -> bool:
    """lengths, q (N, 1, D), k and v caches -> o (N, 1, D)."""
    return re.match(r"^bf16\[\d+,1,\d+\] <- s32\[\d+\] bf16\[\d+,1,\d+\] "
                    r"bf16\[\d+,\d+,\d+\] bf16\[\d+,\d+,\d+\]$", sig) is not None


def cost(ctx):
    flops = nbytes = 0.0
    for block, reps in shapes.layers(ctx.config, "attention"):
        m = block["mixer"]
        H, Hkv, D = m["q_heads"], m["kv_heads"], m["head_dim"]
        for blk in ctx.traced(ctx.rec.blocks):
            lens = blk["lengths"][blk["active"]]
            n = blk["block"]
            keys = sum(n * (int(L) + 1) + n * (n - 1) / 2 for L in lens)
            flops += reps * 4.0 * H * D * keys
            nbytes += reps * BF16 * D * (2 * Hkv * keys
                                         + 2 * H * n * len(lens))
    return flops, nbytes
