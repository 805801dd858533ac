"""Service layer (the step pool and the session lock): the mean time a
request waited before its step began, in milliseconds, from the program's
spans ``pop.pool_wait`` and ``pop.lock_wait``, over the requests that
began before the profiler started."""

from lib import spans


def read(ctx):
    return spans.mean_ms(
        ctx, lambda r: spans.wall_ns(r, "pop.pool_wait", "pop.lock_wait"))
