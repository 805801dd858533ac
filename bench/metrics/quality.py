"""Mean over the window's steps of the domain's headline share of what
was served, computed by the benchmark from the served allocation: for MoE
placement, the share of the gate load served."""

import numpy as np


def read(ctx):
    q = [r["quality"] for r in ctx.records if r.get("quality") is not None]
    return float(np.mean(q)) if q else None
