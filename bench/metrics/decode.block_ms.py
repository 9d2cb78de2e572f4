"""Mean host wall of one decode block (``DecodeEngine.step_block``, which
ends in a device sync) over the blocks that started inside the host span
(in a traced run, before the profiler started)."""


def read(ctx):
    walls = [b["t1"] - b["t0"] for b in ctx.rec.blocks
             if ctx.in_host_span(b["t0"])]
    return 1e3 * sum(walls) / len(walls) if walls else None
