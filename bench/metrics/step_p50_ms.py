"""Open loop: the 50th percentile of step latency in milliseconds, from
each request's due time to its allocation, over every request due in the
window; one that never came counts with the wait it had when the drain
gave up."""

import numpy as np


def read(ctx):
    if not ctx.records:
        return None
    ms = [((r["end"] if r["end"] is not None else ctx.give_up) - r["due"])
          * 1e3 for r in ctx.records]
    return float(np.percentile(ms, 50))
