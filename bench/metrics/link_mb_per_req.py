"""Inter-DC egress per request: the bytes the window's offloaded requests
put on the PrfaaS -> region link (``Request.kv_bytes``: the int8 wire
pytree's size, exactly what ``LinkTopology`` is charged), over all the
window's requests, in MB (1e6 bytes)."""


def read(ctx):
    reqs = ctx.window_requests()
    sent = sum(q.kv_bytes for q in reqs if q.route == "prfaas")
    if not reqs or sent <= 0:
        return None
    return sent / len(reqs) / 1e6
