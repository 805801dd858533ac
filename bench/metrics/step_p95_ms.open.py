"""Open loop: the 95th percentile of step latency in milliseconds, from
each request's due time to its allocation, over the requests due before
the profiler starts (the first ``trace_after_s`` of the window), so that
tracing does not move it; one that never came counts with the wait it had
when the drain gave up."""

import numpy as np


def read(ctx):
    if ctx.stretch is None:
        return None
    ms = [((r["end"] if r["end"] is not None else ctx.give_up) - r["due"])
          * 1e3 for r in ctx.records if r["due"] < ctx.stretch[0]]
    return float(np.percentile(ms, 95)) if ms else None
