"""Pipeline layer (problem, plan, build and warm remap on the host): the
mean time inside the program's ``pop.prepare`` spans in which the step
thread held no CPU (waiting for the interpreter lock, eager device work or
transfers), wall less thread CPU time, in milliseconds, over the requests
that began before the profiler started."""

from lib import spans


def read(ctx):
    return spans.mean_ms(
        ctx, lambda r: sum(s.dur_ns - s.cpu_ns
                           for s in r.get("pop.prepare", ())))
