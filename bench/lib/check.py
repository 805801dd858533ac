"""The comparison that decides ``correct``.

Each checked step's answer is what the program served: the allocation,
the partition of the entities into lanes, each lane's solution of its
sub-LP and the objective the solver reports for it.  The domain's plain
reference (``domains/<domain>.py``) rebuilds every lane's sub-LP from the
instance and solves it with HiGHS in float64, and five numbers are read,
each compared with a limit of its own:

``bad``       answers that are missing or whose lanes are no partition of
              the instance's entities (each exactly once);
``primal``    the largest relative primal residual of a lane's solution,
              ``||(A x - b)+, bound violations||_2 / (1 + ||b||_2)``, the
              solver's own measure, taken on the reference's LP;
``gap``       the largest relative gap between a lane's objective at its
              solution and the reference's optimum,
              ``|c x - opt| / (1 + |c x| + |opt|)``;
``objective`` the largest relative error of the objective the solver
              reports for a lane, ``|reported - c x| / (1 + |c x|)``;
``served``    how far the served allocation is from the one the reference
              derives from the program's own lane solutions.

``primal`` and ``gap`` hold each lane's solution against the reference's
LP and its optimum, on the lanes the solver reports converged: a lane
that ran out of iterations puts its step among the run's ``failed``, and
is held to what it reports and serves.  ``objective`` and ``served`` hold
what the program reports and serves against the lane's solution.

The control is the reference computed in bfloat16, the precision below the
program's float32: each lane's LP data rounded to bfloat16 and solved,
and the solution, its objective and the served allocation rounded to
bfloat16.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np
import scipy.sparse as sparse
from scipy.optimize import linprog

NAMES = ("bad", "primal", "gap", "objective", "served")
# steps of a window that are checked where it has more, besides the hardest
SAMPLE = 24


def answer_of(allocation):
    """What a program step served, as plain arrays, or None where it served
    no solver answer (a fallback)."""
    raw = allocation.raw
    plan = getattr(raw, "plan", None)
    if plan is None or getattr(raw, "x", None) is None:
        return None
    return {"alloc": np.asarray(allocation.alloc),
            "lanes": np.asarray(plan.entity_of_slot),
            "x": np.asarray(raw.x), "obj": np.asarray(raw.sub_objectives),
            "converged": np.asarray(raw.converged, bool)}


def is_partition(lanes: np.ndarray, n: int) -> bool:
    live = lanes[lanes >= 0]
    return bool(np.array_equal(np.sort(live), np.arange(n)))


def _lane_numbers(lp, x, reported, converged: bool) -> dict:
    x = np.asarray(x, np.float64)[: lp.n_var]
    cx = float(lp.c @ x)
    out = {"objective": abs(float(reported) - cx) / (1.0 + abs(cx))}
    if converged:
        opt = lp.optimum()
        viol = np.concatenate([np.maximum(lp.A @ x - lp.b, 0.0),
                               np.maximum(lp.lo - x, 0.0),
                               np.maximum(x - lp.hi, 0.0)])
        out["primal"] = float(np.linalg.norm(viol)
                              / (1.0 + np.linalg.norm(lp.b)))
        out["gap"] = abs(cx - opt) / (1.0 + abs(cx) + abs(opt))
    return out


def numbers(domain, cfg: dict, checked) -> dict:
    """The numbers over ``checked``, a list of (instance, answer)."""
    out = dict.fromkeys(NAMES, 0.0)
    for inst, ans in checked:
        if ans is None or not is_partition(ans["lanes"],
                                           domain.n_entities(inst)):
            out["bad"] += 1
            continue
        lanes = ans["lanes"]
        k = lanes.shape[0]
        lps = [domain.lane_lp(cfg, inst, lanes[i], k) for i in range(k)]
        conv = ans.get("converged", np.ones(k, bool))
        for lp, x, obj, c in zip(lps, ans["x"], ans["obj"], conv):
            for name, v in _lane_numbers(lp, x, obj, bool(c)).items():
                out[name] = max(out[name], v)
        ref = domain.serve(inst, lanes, lps, ans["x"])
        out["served"] = max(out["served"],
                            domain.served_error(ref, ans["alloc"]))
    return out


def verdict(values: dict, limits: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}): each number within its
    limit; a number that is not finite is out of it."""
    checks = {k: {"value": values[k], "limit": limits[k]} for k in NAMES}
    ok = all(np.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in checks.values())
    return ok, checks


# ---------------------------------------------------------------------------
# the control: the reference in bfloat16
# ---------------------------------------------------------------------------

def bf16(a):
    return np.asarray(np.asarray(a, np.float64).astype(ml_dtypes.bfloat16),
                      np.float64)


def _solve_bf16(lp) -> np.ndarray:
    A = sparse.csr_matrix((bf16(lp.A.data), lp.A.indices, lp.A.indptr),
                          shape=lp.A.shape)
    res = linprog(bf16(lp.c), A_ub=A, b_ub=bf16(lp.b),
                  bounds=np.stack([bf16(lp.lo), bf16(lp.hi)], axis=1),
                  method="highs")
    if res.status != 0:
        raise RuntimeError(f"HiGHS (bfloat16 data): {res.message}")
    return bf16(res.x)


def control_answer(domain, cfg: dict, inst: dict) -> dict:
    """The control's answer to one instance: the configuration's k lanes
    dealt round-robin by the domain's scores, each solved in bfloat16."""
    k = int(cfg["k"])
    order = np.argsort(domain.scores(inst), kind="stable")
    n_per = -(-order.shape[0] // k)
    lanes = np.full((k, n_per), -1, np.int64)
    for i in range(k):
        lanes[i, : order[i::k].shape[0]] = order[i::k]
    lps = [domain.lane_lp(cfg, inst, lanes[i], k) for i in range(k)]
    xs = np.stack([_solve_bf16(lp) for lp in lps])
    obj = [bf16(bf16(lp.c) @ x) for lp, x in zip(lps, xs)]
    alloc = domain.serve(inst, lanes, lps, xs, round_to=bf16)
    return {"alloc": alloc, "lanes": lanes, "x": xs, "obj": obj}
