"""Device: the share of the traced stretch of an open window in which no
operation ran on the device, from the profiler's trace."""


def read(ctx):
    t = ctx.trace
    if t is None or t["window_s"] <= 0:
        return None
    return (1.0 - t["busy_s"] / t["window_s"]) * 100.0
