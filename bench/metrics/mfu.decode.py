"""Model FLOPs of the traced decode blocks (active slots' tokens only) over
the device time of the decode-block program in the trace, as a share of
the chip's bf16 peak."""
from bench import flops

PROGRAMS = ("jit__block_impl",)


def read(ctx):
    seconds = ctx.module_seconds(PROGRAMS)
    if not ctx.peak or seconds <= 0:
        return None
    f = sum(flops.decode_block(ctx.config, b["lengths"][b["active"]],
                               b["block"])
            for b in ctx.traced(ctx.rec.blocks))
    return 100.0 * f / seconds / ctx.peak["bf16_flops"] if f > 0 else None
