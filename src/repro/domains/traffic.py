"""WAN traffic engineering as a registered domain (paper §3.2).

The LP/entity model lives in ``problems/traffic_engineering.py``
(:class:`TrafficProblem` — commodities are entities, per-path flows the
variables; every sub-problem keeps the whole network at 1/k capacity).
The domain instance IS the problem object: it already bundles topology,
demands and precomputed paths, and rebuilding it per tick is how demand
drift enters.
"""

from __future__ import annotations

from ..core.config import ExecConfig, SolveConfig
from ..problems.traffic_engineering import TrafficProblem
from .base import DomainSpec
from .registry import register

SPEC = register(DomainSpec(
    name="traffic",
    instance_types=(TrafficProblem,),
    describe="max-total-flow WAN TE (commodities onto k-shortest paths)",
    problem=lambda inst: inst,
    # the SLO tuner's quality scalar (repro.tuning)
    quality=lambda m: m["total_flow"],
    default_solve=SolveConfig(k=8, strategy="stratified"),
    # the primal tolerance bounds the L2 norm of all overdraws relative to
    # the capacities' norm: at 1e-4 single links ran 0.14% over capacity,
    # at 1e-5 they stay within 0.03% (3,000-4,000 demands, k=4)
    default_exec=ExecConfig(solver_kw=dict(
        max_iters=8_000, tol_primal=1e-5, tol_gap=1e-4)),
))
