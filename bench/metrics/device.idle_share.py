"""Share of the traced time with work to serve in which no operation ran
on the device, in %: the device's idle time, less the idle time in which
the host had no request to serve and waited for the next arrival
(``bench.wait_arrival``), over the traced window less that waiting time.
So a quiet phase of the traffic does not read as the host holding the
device back."""


def read(ctx):
    tw = ctx.rec.trace_window
    if not ctx.trace or not ctx.trace["devices"] or tw is None \
            or tw[1] is None:
        return None
    worked = (tw[1] - tw[0]) - ctx.trace["waiting_s"]
    if worked <= 0:
        return None
    idle = worked - ctx.trace["busy_s"]
    return 100.0 * max(0.0, idle) / worked
