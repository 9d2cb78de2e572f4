"""Roofline share of the ``flash_attn`` kernel: least time for its traced calls at
the chip's peaks (bench/kernels/flash_attn.py) over its device time, in %."""


def read(ctx):
    return ctx.roofline("flash_attn")
