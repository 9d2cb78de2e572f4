"""Roofline share of the ``decode_attn`` kernel: least time for its traced calls at
the chip's peaks (bench/kernels/decode_attn.py) over its device time, in %."""


def read(ctx):
    return ctx.roofline("decode_attn")
