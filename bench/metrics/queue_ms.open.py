"""Dispatcher layer (the micro-batching queue): the mean time a request's
ticket waited between its ``put`` and the start of the launch that served
it, in milliseconds, from the program's ``pop.queue`` spans, over the
requests that began before the profiler started."""

from lib import spans


def read(ctx):
    return spans.mean_ms(ctx, lambda r: spans.wall_ns(r, "pop.queue"))
