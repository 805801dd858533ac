"""MoE expert placement: POP vs full vs greedy — the fourth scenario's
quality/runtime row (onboarded through the domain registry alone).

Acceptance: POP at k>=4 lands within 1.5% of the unpartitioned
``solve_full`` objective (served gate load net of migration penalty)
while running the k-lane map step; greedy serves similar load but
migrates nearly the whole expert fleet.

    PYTHONPATH=src python -m benchmarks.bench_moe_placement [--fast]
"""

from __future__ import annotations

import argparse

import numpy as np

from repro.core import SolveConfig
from repro.domains import (greedy_placement, make_placement_instance,
                           place_experts)
from repro.domains.moe_placement import _evaluate
from .common import Timer, emit, save_json, use_compile_cache


def run(n_experts: int = 512, n_devices: int = 16, ks=(4, 8),
        seed: int = 0) -> dict:
    inst = make_placement_instance(n_experts, n_devices, seed=seed)
    rows = []

    with Timer() as t_full:
        _, _, ev_full = place_experts(inst, solve_cfg=SolveConfig(k=1))
    rows.append(dict(method="full", k=1, solve_s=t_full.seconds,
                     **{k: v for k, v in ev_full.items()}))
    emit("moe_placement_full", t_full.seconds * 1e6,
         f"objective={ev_full['objective']:.1f};"
         f"served={ev_full['served_fraction']:.3f};"
         f"moved={ev_full['n_moved']}")

    for k in ks:
        with Timer() as t:
            _, res, ev = place_experts(
                inst, solve_cfg=SolveConfig(k=k, strategy="stratified"))
        ratio = ev["objective"] / max(ev_full["objective"], 1e-9)
        rows.append(dict(method=f"pop{k}", k=k, solve_s=t.seconds,
                         obj_ratio=ratio, backend=res.backend,
                         engine=res.engine, **{k2: v for k2, v in ev.items()}))
        emit(f"moe_placement_pop{k}", t.seconds * 1e6,
             f"obj_ratio={ratio:.4f};served={ev['served_fraction']:.3f};"
             f"moved={ev['n_moved']};speedup="
             f"{t_full.seconds/max(t.seconds, 1e-9):.1f}x")

    with Timer() as t_g:
        ev_g = _evaluate(inst, greedy_placement(inst))
    rows.append(dict(method="greedy", k=0, solve_s=t_g.seconds,
                     obj_ratio=ev_g["objective"] / max(ev_full["objective"],
                                                       1e-9),
                     **{k: v for k, v in ev_g.items()}))
    emit("moe_placement_greedy", t_g.seconds * 1e6,
         f"obj_ratio={ev_g['objective']/max(ev_full['objective'], 1e-9):.4f};"
         f"moved={ev_g['n_moved']}")

    out = {"n_experts": n_experts, "n_devices": n_devices, "rows": rows}
    save_json("moe_placement", out)
    return out


if __name__ == "__main__":
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    args = ap.parse_args()
    if args.fast:
        run(n_experts=128, n_devices=8)
    else:
        run()
