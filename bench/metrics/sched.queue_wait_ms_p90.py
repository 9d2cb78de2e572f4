"""p90 over the window's requests of due -> start of the prefill unit that
carries the request (host clock), for units that started inside the host
span (in a traced run, before the profiler started)."""
from bench.context import p90


def read(ctx):
    return p90([(q.unit_start - q.due) * 1e3 for q in ctx.window_requests()
                if ctx.in_host_span(q.unit_start)])
