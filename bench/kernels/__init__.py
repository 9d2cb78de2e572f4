"""One cost file per kernel: the FLOPs and bytes its calls in the traced
window need, from the shapes the driver recorded.

Each ``bench/kernels/<kernel>.py`` defines ``match(sig)``, which tells the
kernel's calls in the trace by their operand signature (the trace carries
no kernel names), and ``cost(ctx) -> (flops, bytes)`` summed over the calls
of the traced window; ``bench/metrics/<kernel>_roofline.py`` divides
the least time those need at the chip's peaks by the kernel's device time.
"""
