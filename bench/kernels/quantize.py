"""int8 wire quantization (``kernels/quantize.py``).

Per offloaded request, each attention layer group's k and v leaves of
(layers, 1, L, Hkv, D) float32 are quantized per tensor: the least bytes
are one read of the float32 leaf and one write of its int8 codes; FLOPs
(absmax, scale, round) are about 3 per element.
"""
from bench import shapes



def match(sig: str) -> bool:
    """int8 codes out."""
    return "s8[" in sig.partition(" <- ")[0]


def cost(ctx):
    flops = nbytes = 0.0
    for q in ctx.traced_requests(route="prfaas"):
        for block, reps in shapes.layers(ctx.config, "attention"):
            m = block["mixer"]
            n = 2 * reps * q.prompt_len * m["kv_heads"] * m["head_dim"]
            flops += 3.0 * n
            nbytes += 5.0 * n
    return flops, nbytes
