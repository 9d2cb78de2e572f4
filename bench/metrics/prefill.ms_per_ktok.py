"""Prefill wall per thousand prompt tokens in the window: bucketed units
by the program's own synced wall (``PrefillEngine.prefill``), chunked
prefills by the host wall of each chunk step (the last one syncs), over
the valid prompt tokens they computed; units and chunks that started in
the host span (in a traced run, before the profiler started)."""


def read(ctx):
    units = [u for u in ctx.rec.units if ctx.in_host_span(u["t0"])]
    chunks = [c for c in ctx.rec.chunks if ctx.in_host_span(c["t0"])]
    wall = sum(u["wall"] for u in units) + sum(c["t1"] - c["t0"]
                                               for c in chunks)
    tokens = sum(int(u["lengths"].sum()) for u in units) + sum(
        int(min(max(c["lengths"][0] - c["index"] * c["chunk"], 0),
                c["chunk"])) for c in chunks)
    if tokens <= 0:
        return None
    return wall / tokens * 1e6
