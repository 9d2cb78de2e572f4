"""Causal flash attention of prefill (``kernels/flash_attn.py``).

Per call on a (B, H, Sq, D) query block over Sk keys at offset Sk - Sq:
FLOPs 4 * B * H * D * pairs, pairs = Sq * (Sk - Sq) + Sq * (Sq + 1) / 2
(only causal pairs); bytes: q and o (B, H, Sq, D) and k, v (B, Hkv, Sk, D)
in bf16.  A bucketed unit calls it once per attention layer at its padded
(batch, length) bucket; a chunked prefill's chunk i at Sq = C, Sk = (i+1) C.
"""
from bench import shapes

BF16 = 2


def match(sig: str) -> bool:
    """q, k, v in, o out: three bf16 operands, no lengths."""
    out, _, args = sig.partition(" <- ")
    ops = args.split(" ")
    return (out.startswith("bf16[") and len(ops) == 3
            and all(o.startswith("bf16[") for o in ops))


def call(B, H, Hkv, D, Sq, Sk):
    pairs = Sq * (Sk - Sq) + Sq * (Sq + 1) / 2
    flops = 4.0 * B * H * D * pairs
    nbytes = BF16 * D * B * (2 * H * Sq + 2 * Hkv * Sk)
    return flops, nbytes


def cost(ctx):
    flops = nbytes = 0.0
    for block, reps in shapes.layers(ctx.config, "attention"):
        m = block["mixer"]
        H, Hkv, D = m["q_heads"], m["kv_heads"], m["head_dim"]
        for u in ctx.traced(ctx.rec.units):
            B = shapes.next_pow2(len(u["lengths"]))
            f, b = call(B, H, Hkv, D, u["bucket"], u["bucket"])
            flops, nbytes = flops + reps * f, nbytes + reps * b
        for c in ctx.traced(ctx.rec.chunks):
            C = c["chunk"]
            f, b = call(c["batch"], H, Hkv, D, C, (c["index"] + 1) * C)
            flops, nbytes = flops + reps * f, nbytes + reps * b
    return flops, nbytes
