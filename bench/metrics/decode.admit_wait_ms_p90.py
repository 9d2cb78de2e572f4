"""p90 over the window's requests of first token -> admission into a decode
slot (host clock), for admissions inside the host span (in a traced run,
before the profiler started)."""
from bench.context import p90


def read(ctx):
    return p90([(q.admit - q.first) * 1e3 for q in ctx.window_requests()
                if q.first is not None and ctx.in_host_span(q.admit)])
