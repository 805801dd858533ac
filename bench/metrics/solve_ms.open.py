"""Solver layer: the mean solve time, in milliseconds, of the steps due in
the traced stretch of an open window, as each step's allocation reports
it (the stacked PDHG launch, timed by the program to its results)."""

import numpy as np


def read(ctx):
    if ctx.stretch is None:
        return None
    s, e = ctx.stretch
    t = [r["solve_time_s"] for r in ctx.records
         if s <= r["due"] <= e and r.get("solve_time_s") is not None]
    return float(np.mean(t)) * 1e3 if t else None
