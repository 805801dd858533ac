"""Gavel cluster scheduling as a registered domain (paper §3.1).

The LP/entity model lives in ``problems/cluster_scheduling.py``
(:class:`GavelProblem` — jobs are entities, combos the variables); this
module is the declarative registration that lets the scheduler enter
through ``PopService.session(...).step(...)`` like every other scenario.

A step's instance is a :class:`GavelInstance`: the measured workload
(throughputs, priorities, worker counts) plus the stable job ids that let
warm starts survive job churn between scheduling rounds.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from ..core.config import ExecConfig, SolveConfig
from ..problems.cluster_scheduling import ClusterWorkload, GavelProblem
from .base import DomainSpec
from .registry import register


@dataclasses.dataclass
class GavelInstance:
    """One scheduling round's input: the fleet as measured right now."""

    wl: ClusterWorkload
    space_sharing: bool = False
    # stable external job ids (None = positional): what warm-start
    # remapping matches on when jobs are submitted/removed between rounds
    job_ids: Optional[np.ndarray] = None

    @property
    def n_jobs(self) -> int:
        return self.wl.T.shape[0]


def _problem(inst: GavelInstance) -> GavelProblem:
    return GavelProblem(inst.wl, space_sharing=inst.space_sharing)


def _evaluate(inst: GavelInstance, rho: np.ndarray) -> dict:
    rho = np.atleast_1d(rho)
    return {
        "mean_norm_throughput": float(rho.mean()),
        "min_norm_throughput": float(rho.min()),
        "p10_norm_throughput": float(np.percentile(rho, 10)),
    }


SPEC = register(DomainSpec(
    name="gavel",
    instance_types=(GavelInstance,),
    describe="max-min fair cluster scheduling (jobs onto accelerator types)",
    problem=_problem,
    entity_ids=lambda inst: inst.job_ids,
    evaluate=_evaluate,
    # the SLO tuner's quality scalar (repro.tuning): the paper's headline
    # objective for this domain
    quality=lambda m: m["mean_norm_throughput"],
    # the scheduler's historical operating point: stratified splits, POP
    # only once the fleet has >= 8 jobs per sub-problem
    default_solve=SolveConfig(k=8, strategy="stratified", min_per_sub=8),
    # the cap leaves room for slow lanes: one lane of the 448-job
    # space-sharing fleet (benchmarks/run.py's default) needs 21,160
    default_exec=ExecConfig(solver_kw=dict(
        max_iters=40_000, tol_primal=1e-4, tol_gap=1e-4, equilibrate=True)),
))
