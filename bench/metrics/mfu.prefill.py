"""Model FLOPs of the traced prefill units (valid prompt tokens only, see
bench/flops.py) over the device time of the prefill programs in the trace,
as a share of the chip's bf16 peak."""
from bench import flops

PROGRAMS = ("jit__prefill_impl", "jit_prefill_chunk", "jit__carry_last_impl",
            "jit__finish_impl")


def read(ctx):
    seconds = ctx.module_seconds(PROGRAMS)
    if not ctx.peak or seconds <= 0:
        return None
    f = sum(flops.prefill(ctx.config, int(L))
            for u in ctx.traced(ctx.rec.units) for L in u["lengths"])
    for c in ctx.traced(ctx.rec.chunks):
        C, i = c["chunk"], c["index"]
        for L in c["lengths"][:1]:
            end = min(int(L), (i + 1) * C)
            f += flops.prefill(ctx.config, end, i * C,
                               with_head=end == int(L))
    return 100.0 * f / seconds / ctx.peak["bf16_flops"] if f > 0 else None
