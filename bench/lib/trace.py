"""Reduces a profiler trace (``.xplane.pb``) to device busy time, the device
operations that took longest, and the device's idle gaps, each named by
what the host was doing in it.

The traced stretch is the benchmark's own host span ``bench.traced``; busy
time is the union of the intervals of the device's ``XLA Ops`` events
inside it, averaged over the devices in the trace.  An idle gap of at
least ``GAP_NS`` is named by the innermost benchmark span (``bench.*``)
that holds its midpoint, and by the shortest other host event that holds
it; shorter gaps are counted together as op-to-op gaps.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict

import numpy as np

STRETCH = "bench.traced"
GAP_NS = 50_000
TOP = 10


def _events(line):
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for e in line.events]


def op_name(hlo: str) -> str:
    """``fusion.12`` of ``%fusion.12 = f32[...] fusion(...), ...``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def _union(iv: np.ndarray) -> np.ndarray:
    """Merged, sorted [start, end] rows of possibly overlapping ones."""
    if iv.shape[0] == 0:
        return iv
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    end = np.maximum.accumulate(iv[:, 1])
    new = np.ones(iv.shape[0], bool)
    new[1:] = iv[1:, 0] > end[:-1]
    starts = iv[new, 0]
    group = np.cumsum(new) - 1
    ends = np.zeros(starts.shape[0])
    np.maximum.at(ends, group, end)
    return np.stack([starts, ends], axis=1)


def latest_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def reduce_trace(path: str):
    """``{"busy_s", "window_s", "device_ops", "idle_gaps"}`` for the trace
    at ``path``, or None where it holds no device operations or no
    traced stretch."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops = [_events(ln) for ln in plane.lines if ln.name == "XLA Ops"]
            if ops:
                devices.append([ev for line in ops for ev in line])
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend(_events(ln))
    stretch = [(s, e) for s, e, n in host if n == STRETCH]
    devices = [d for d in devices if d]
    if not stretch or not devices:
        return None
    w0, w1 = min(s for s, _ in stretch), max(e for _, e in stretch)
    window_ns = w1 - w0

    busy, op_time = [], defaultdict(float)
    first_busy = None
    for evs in devices:
        iv = np.array([(s, e) for s, e, _ in evs], np.float64)
        iv = np.clip(iv, w0, w1)
        iv = iv[iv[:, 1] > iv[:, 0]]
        merged = _union(iv)
        busy.append(float((merged[:, 1] - merged[:, 0]).sum()))
        if first_busy is None:
            first_busy = merged
        for s, e, name in evs:
            d = min(e, w1) - max(s, w0)
            if d > 0:
                op_time[op_name(name)] += d / len(devices)
    if not any(busy):
        return None

    # idle gaps of the first device, named by what the host was doing
    edges = np.concatenate([[w0], first_busy.ravel(), [w1]]).reshape(-1, 2)
    gaps = edges[edges[:, 1] > edges[:, 0]]
    hs = np.array([s for s, _, _ in host], np.float64)
    he = np.array([e for _, e, _ in host], np.float64)
    hn = [n for _, _, n in host]
    named = np.array([n.startswith("bench.") for n in hn], bool)
    is_bench = named & np.array([n != STRETCH for n in hn], bool)
    idle = defaultdict(float)
    for g0, g1 in gaps:
        length = g1 - g0
        if length < GAP_NS:
            idle["op-to-op gaps under 50 us"] += length
            continue
        mid = 0.5 * (g0 + g1)
        holds = (hs <= mid) & (he >= mid)
        span = _shortest(holds & is_bench, hs, he, hn) or "outside bench spans"
        other = _shortest(holds & ~named & (he - hs > 0), hs, he, hn)
        idle[span if other is None else f"{span} / {other}"] += length

    def top(d):
        return [[k, float(v) / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {"busy_s": float(np.mean(busy)) / 1e9,
            "window_s": window_ns / 1e9,
            "device_ops": top(op_time), "idle_gaps": top(idle)}


def _shortest(mask, hs, he, hn):
    idx = np.flatnonzero(mask)
    if idx.size == 0:
        return None
    return hn[idx[np.argmin(he[idx] - hs[idx])]]
