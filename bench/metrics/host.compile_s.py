"""Seconds of backend compiles that ended inside the host span
(``jax.monitoring``; in a traced run, before the profiler started); 0 when
the set-up warmed every program the window runs."""


def read(ctx):
    return sum(s for t, s in ctx.rec.compiles if ctx.in_host_span(t))
