"""Chunked gated linear attention of Mamba2 prefill (``kernels/gla.py``).

Per call on (B, H, S) with key dim dk, value dim dv and chunk c = 64, per
chunk and head: causal intra-chunk scores and weighted values,
2 * c(c+1)/2 * (dk + dv) FLOPs, and the carried state's readout and update,
4 * c * dk * dv.  Bytes: q, k, v and o in bf16, the decays in float32, the
state in and out in float32.
"""
from bench import shapes

CHUNK = 64
BF16, F32 = 2, 4


def match(sig: str) -> bool:
    """lengths, q, k, v, decay rows, state -> (o, state): six operands
    (the delta rule has a seventh, beta)."""
    out, _, args = sig.partition(" <- ")
    ops = args.split(" ")
    return (out.startswith("(bf16[") and len(ops) == 6
            and ops[0].startswith("s32[") and ops[-1].startswith("f32["))


def call(B, H, S, dk, dv, c=CHUNK):
    n = -(-S // c)
    flops = B * H * n * (c * (c + 1) * (dk + dv) + 4.0 * c * dk * dv)
    nbytes = B * H * (BF16 * S * (2 * dk + 2 * dv) + F32 * S
                      + 2 * F32 * dk * dv)
    return flops, nbytes


def cost(ctx):
    flops = nbytes = 0.0
    for block, reps in shapes.layers(ctx.config, "mamba2"):
        m = block["mixer"]
        H, dk, dv = m["heads"], m["key_dim"], m["value_dim"]
        for u in ctx.traced(ctx.rec.units):
            B = shapes.next_pow2(len(u["lengths"]))
            f, b = call(B, H, u["bucket"], dk, dv)
            flops, nbytes = flops + reps * f, nbytes + reps * b
        for c in ctx.traced(ctx.rec.chunks):
            f, b = call(c["batch"], H, c["chunk"], dk, dv)
            flops, nbytes = flops + reps * f, nbytes + reps * b
    return flops, nbytes
