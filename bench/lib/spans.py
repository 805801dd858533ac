"""Reads the program's own span log (``repro.obs``) for the per-layer
metrics of an open window.

The requests counted are those whose root span ``pop.step`` began in the
window before the profiler started, ``[ctx.t0, ctx.stretch[0])``, so the
profiler's cost stays out of these numbers.  Everything here returns None
where there is nothing to read: no traced stretch, a program without
``repro.obs``, or a ring that dropped spans of the window.
"""

from __future__ import annotations


def window_stages(ctx):
    """``[{span name: [Span, ...]}]``, one dict per request counted, of
    the spans carrying its request id; or None."""
    if ctx.stretch is None:
        return None
    try:
        from repro import obs
    except ImportError:
        return None
    recs, wrapped = obs.spans()
    lo, hi = ctx.t0 * 1e9, ctx.stretch[0] * 1e9
    # the ring keeps spans in the order they ended, and every span of a
    # counted request ends after ctx.t0: if the oldest kept one ended
    # before it, nothing of the window was dropped
    if wrapped and (not recs or recs[0].t1_ns >= lo):
        return None
    rids = {s.rid for s in recs
            if s.name == "pop.step" and lo <= s.t0_ns < hi}
    stages = {rid: {} for rid in rids}
    for s in recs:
        if s.rid in stages:
            stages[s.rid].setdefault(s.name, []).append(s)
    return list(stages.values()) or None


def mean_ms(ctx, value) -> float:
    """Mean over the counted requests of ``value(stages)`` nanoseconds, in
    milliseconds; None where no request is counted."""
    reqs = window_stages(ctx)
    if reqs is None:
        return None
    return sum(value(r) for r in reqs) / len(reqs) / 1e6


def wall_ns(stages: dict, *names) -> int:
    """Summed duration of a request's spans of these names."""
    return sum(s.dur_ns for n in names for s in stages.get(n, ()))
