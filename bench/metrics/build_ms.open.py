"""Pipeline layer (plan, build and stack of the sub-LPs on the host): the
mean build time of the steps due in the traced stretch of an open window,
in milliseconds, as each step's allocation reports it."""

import numpy as np


def read(ctx):
    if ctx.stretch is None:
        return None
    s, e = ctx.stretch
    b = [r["build_time_s"] for r in ctx.records
         if s <= r["due"] <= e and r.get("build_time_s") is not None]
    return float(np.mean(b)) * 1e3 if b else None
