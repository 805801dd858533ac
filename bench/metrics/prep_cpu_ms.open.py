"""Pipeline layer (problem, plan, build and warm remap on the host): the
mean CPU time the step thread spent inside the program's ``pop.prepare``
spans, in milliseconds, over the requests that began before the profiler
started."""

from lib import spans


def read(ctx):
    return spans.mean_ms(
        ctx, lambda r: sum(s.cpu_ns for s in r.get("pop.prepare", ())))
