"""Host time of the int8 wire per offloaded request: the program's
``prfaas.wire`` spans (``repro.serving.spans``: quantize, link submit and
dequantize of one offloaded request's trimmed cache) that started inside
the host span (in a traced run, before the profiler started), their summed
duration over their count, in ms.  None where the program keeps no such
spans or no request was offloaded in the host span."""


def read(ctx):
    try:
        from repro.serving import spans
    except ImportError:
        return None
    walls = [t1 - t0 for name, t0, t1, _ in spans.recent()
             if name == "prfaas.wire" and ctx.in_host_span(t0)]
    return 1e3 * sum(walls) / len(walls) if walls else None
