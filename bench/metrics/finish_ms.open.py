"""Pipeline layer (reduce, rounding and metrics of the served allocation
on the host): the mean time inside the program's ``pop.finish`` spans, in
milliseconds, over the requests that began before the profiler started."""

from lib import spans


def read(ctx):
    return spans.mean_ms(ctx, lambda r: spans.wall_ns(r, "pop.finish"))
