"""Gavel-style heterogeneous cluster scheduling (paper §3.1).

Max-min fair allocation of heterogeneous accelerators to DL jobs, with
optional *space sharing* (two jobs concurrently on one accelerator — the
paper's 10^6-job-combination configuration).

LP (epigraph form, per DESIGN.md §2 — PDHG solves (X, t) jointly):

    maximize t
    s.t.     t <= scale_m * sum_{c∋m, j} T[c, j, slot_m] X[c, j]   ∀ jobs m
             sum_{c∋m, j} X[c, j] <= 1                             ∀ jobs m
             sum_c z_c X[c, j] <= num_workers_j * frac             ∀ types j
             0 <= X <= 1

where c ranges over job *combos* — singletons, plus unordered pairs when
space sharing is on.  scale_m = 1 / (w_m * max_j T_mj) normalises each
job's throughput to [0, 1] so the max-min is over *fair-share-relative*
rates, matching Gavel's heterogeneity-aware LP shape.

The constraint operator is STRUCTURED (segment-sum over combo membership;
no dense K is ever built): the full 10^6-combo problem has ~3x10^6
variables, far past dense range, and this is exactly the regime the paper
targets.  POP partitions *jobs* (combos are then intra-subset pairs, giving
the k^2 variable reduction of paper Fig. 2) and splits worker counts.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.pdhg import OperatorLP, structured_from_coo
from ..core.plan import SubLayout
from ..core.pop import POPProblem


# ---------------------------------------------------------------------------
# workload generation (Gavel-like: 3 accelerator generations)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ClusterWorkload:
    T: np.ndarray            # [n_jobs, n_types] raw throughputs
    w: np.ndarray            # [n_jobs] priorities
    z: np.ndarray            # [n_jobs] workers requested
    num_workers: np.ndarray  # [n_types]
    interference: np.ndarray  # [n_jobs] space-sharing throughput retention in (0,1]
    job_type: np.ndarray     # [n_jobs] int label (for clustered partitions)


def make_cluster_workload(n_jobs: int, num_workers=(256, 256, 256),
                          seed: int = 0) -> ClusterWorkload:
    """Synthetic Gavel-like workload: job archetypes with distinct
    speedup profiles across 3 accelerator generations (V100/P100/K80-ish)."""
    rng = np.random.default_rng(seed)
    archetypes = np.array([
        # relative throughput on [v100, p100, k80]
        [1.00, 0.45, 0.25],   # attention-heavy
        [1.00, 0.60, 0.35],   # conv-heavy
        [1.00, 0.80, 0.60],   # small model / input-bound
        [1.00, 0.35, 0.10],   # tensor-core-dependent
    ])
    jt = rng.integers(0, len(archetypes), n_jobs)
    base = rng.lognormal(0.0, 0.5, n_jobs)[:, None]
    T = archetypes[jt] * base * rng.uniform(0.9, 1.1, (n_jobs, 3))
    w = rng.choice([1.0, 2.0, 4.0], n_jobs, p=[0.7, 0.2, 0.1])
    z = np.ones(n_jobs)
    interference = rng.uniform(0.55, 0.95, n_jobs)
    return ClusterWorkload(T=T, w=w, z=z,
                           num_workers=np.asarray(num_workers, np.float64),
                           interference=interference, job_type=jt)


# ---------------------------------------------------------------------------
# structured constraint operator
# ---------------------------------------------------------------------------

def _k_mv(data, x):
    """K x for the epigraph LP.  Layout of x: [X_flat (C*R), t].

    Row blocks:  [epigraph (n), time (n), workers (R)]

    ``seg`` is a [n_jobs+1] prototype array carrying the (static) job count
    in its SHAPE — jit-safe where a plain int leaf would become a tracer.
    """
    S, member, z, seg = data             # S: [C, R, 2] scaled T; member: [C, 2]
    n_jobs = seg.shape[0] - 1
    C, R, _ = S.shape
    X = x[: C * R].reshape(C, R)
    t = x[C * R]

    # per-(combo, slot) scaled throughput contribution
    contrib = jnp.einsum("crs,cr->cs", S, X,
                         precision=jax.lax.Precision.HIGHEST)  # [C, 2]
    thpt = jax.ops.segment_sum(contrib.reshape(-1), member.reshape(-1),
                               num_segments=n_jobs + 1)[:n_jobs]
    # time: each combo occurrence consumes the member's time budget
    time_c = X.sum(axis=1)                                # [C]
    occ = jnp.broadcast_to(time_c[:, None], member.shape).reshape(-1)
    time = jax.ops.segment_sum(occ, member.reshape(-1),
                               num_segments=n_jobs + 1)[:n_jobs]
    workers = (z[:, None] * X).sum(axis=0)                # [R]
    return jnp.concatenate([t - thpt, time, workers])


def _kt_mv(data, y):
    """K^T y.  y layout: [y_ep (n), y_time (n), y_work (R)]."""
    S, member, z, seg = data
    n_jobs = seg.shape[0] - 1
    C, R, _ = S.shape
    y_ep = y[:n_jobs]
    y_time = y[n_jobs: 2 * n_jobs]
    y_work = y[2 * n_jobs: 2 * n_jobs + R]

    y_ep_pad = jnp.concatenate([y_ep, jnp.zeros(1, y.dtype)])
    y_time_pad = jnp.concatenate([y_time, jnp.zeros(1, y.dtype)])
    ep_m = y_ep_pad[member]                               # [C, 2]
    tm_m = y_time_pad[member]                             # [C, 2]

    gX = (-jnp.einsum("crs,cs->cr", S, ep_m,
                      precision=jax.lax.Precision.HIGHEST)
          + tm_m.sum(axis=1)[:, None]
          + z[:, None] * y_work[None, :])
    gt = y_ep.sum()
    return jnp.concatenate([gX.reshape(-1), gt[None]])


# ---------------------------------------------------------------------------
# POP problem
# ---------------------------------------------------------------------------

class GavelProblem(POPProblem):
    """Max-min fair scheduling, POP-partitioned over JOBS."""

    K_mv = staticmethod(_k_mv)
    KT_mv = staticmethod(_kt_mv)

    def __init__(self, wl: ClusterWorkload, space_sharing: bool = False,
                 leftover_bonus: float = 0.05, coef_dtype: str = "float32"):
        self.wl = wl
        self.space_sharing = space_sharing
        self.n_entities = wl.T.shape[0]
        self.n_types = wl.T.shape[1]
        # ELL coefficient storage for the structured metadata
        # (core/pdhg.quantize_structured: "float32"/"bfloat16"/"int8")
        self.coef_dtype = coef_dtype
        self.scale = 1.0 / (wl.w * wl.T.max(axis=1))
        # secondary water-filling term: after the min is maximised, spend
        # leftover capacity on mean throughput (objective stays linear)
        self.leftover_bonus = leftover_bonus

    # --- partitioning hooks -------------------------------------------------
    def entity_attrs(self):
        return np.concatenate([
            self.wl.T * self.scale[:, None],
            self.wl.w[:, None], self.wl.z[:, None],
        ], axis=1)

    def entity_scores(self):
        return self.wl.w * self.wl.z

    def sub_layout(self, n_slots: int) -> SubLayout:
        """Warm-start remap layout (``core/plan.py``).

        x = [X_flat (C*R), t] with singleton combos FIRST (``_combos``), so
        slot ``s`` owns X[s, :] — the job's own allocation row.  Pair-combo
        variables (space sharing) have no single owner and restart cold on
        a remap.  Rows: [epigraph (n), time (n), workers (R)] — the first
        two move with their job, the worker-cap rows are lane-global.
        """
        R = self.n_types
        C = n_slots
        if self.space_sharing:
            C += n_slots * (n_slots - 1) // 2
        x_slot = np.arange(n_slots)[:, None] * R + np.arange(R)[None, :]
        y_slot = np.stack([np.arange(n_slots), n_slots + np.arange(n_slots)],
                          axis=1)
        return SubLayout(x_slot=x_slot, y_slot=y_slot,
                         x_global=np.array([C * R]),
                         y_global=2 * n_slots + np.arange(R))

    # --- combo construction -------------------------------------------------
    def _combos(self, ids: np.ndarray):
        """Singleton + (if space sharing) within-subset pair combos.
        ids may contain -1 padding (kept as dead combos)."""
        n = ids.shape[0]
        singles = np.stack([ids, np.full(n, -1)], axis=1)
        if not self.space_sharing:
            return singles
        iu, ju = np.triu_indices(n, k=1)
        pairs = np.stack([ids[iu], ids[ju]], axis=1)
        # a pair is dead if either member is padding
        dead = (pairs < 0).any(axis=1)
        pairs[dead] = -1
        return np.concatenate([singles, pairs], axis=0)

    def _structured(self, S: np.ndarray, member: np.ndarray, z: np.ndarray,
                    n_local: int):
        """ELL index metadata (``core/pdhg.StructuredOperator``) for the
        singleton-combo operator — what lets ``engine="fused_structured"``
        run the per-job segment-sums as batched gather/segment-reduce
        kernels.  Pair combos (space sharing) make the worker rows ~C wide
        (C ~ n^2/2): genuinely dense-in-X rows where ELL padding loses to
        the matvec engine, so space-sharing builds skip the metadata."""
        C, R, _ = S.shape
        n = n_local
        mem = np.broadcast_to(member[:, None, :], (C, R, 2))
        xcol = np.broadcast_to(
            (np.arange(C)[:, None] * R + np.arange(R)[None, :])[:, :, None],
            (C, R, 2))
        valid = mem < n                               # dump slot = n
        # epigraph rows: +1 on t, -S[c, r, s] on each member's X entries
        rows = [np.arange(n), mem[valid], n + mem[valid]]
        cols = [np.full(n, C * R), xcol[valid], xcol[valid]]
        vals = [np.ones(n), -S[valid], np.ones(int(valid.sum()))]
        # worker rows: z_c on X[c, r]
        live = np.broadcast_to((z != 0)[:, None], (C, R))
        rows.append((2 * n + np.broadcast_to(np.arange(R)[None, :],
                                             (C, R)))[live])
        cols.append((np.arange(C)[:, None] * R
                     + np.arange(R)[None, :])[live])
        vals.append(np.broadcast_to(z[:, None], (C, R))[live])
        return structured_from_coo(
            np.concatenate(rows), np.concatenate(cols), np.concatenate(vals),
            2 * n + R, C * R + 1, coef_dtype=self.coef_dtype)

    def _build(self, combos_global: np.ndarray, local_of, n_local: int,
               frac: float, scale_vec: Optional[np.ndarray]) -> OperatorLP:
        wl = self.wl
        C = combos_global.shape[0]
        R = self.n_types
        S = np.zeros((C, R, 2))
        member = np.full((C, 2), n_local, np.int64)       # dump slot
        z = np.zeros(C)
        valid0 = combos_global[:, 0] >= 0
        g0 = np.maximum(combos_global[:, 0], 0)
        g1 = np.maximum(combos_global[:, 1], 0)
        is_pair = combos_global[:, 1] >= 0

        # slot 0
        S[valid0, :, 0] = (wl.T[g0] * self.scale[g0, None])[valid0]
        member[valid0, 0] = local_of(combos_global[valid0, 0])
        # slot 1 (pairs): both jobs retain interference-scaled throughput
        inter = np.sqrt(wl.interference[g0] * wl.interference[g1])
        S[is_pair, :, 0] *= inter[is_pair, None]
        S[is_pair, :, 1] = (wl.T[g1] * self.scale[g1, None] *
                            inter[:, None])[is_pair]
        member[is_pair, 1] = local_of(combos_global[is_pair, 1])
        z[valid0] = wl.z[g0][valid0]                      # pairs share workers

        n_var = C * R + 1
        c = np.zeros(n_var); c[-1] = -1.0                 # max t
        # secondary: -bonus/n * sum_m rho_m  (keeps max-min primary)
        c[: C * R] = -(self.leftover_bonus / max(n_local, 1)) * S.sum(axis=2).reshape(-1)
        l = np.zeros(n_var)
        u = np.zeros(n_var)
        u[: C * R] = np.repeat(valid0.astype(np.float64), R)
        u[-1] = 10.0
        # replication (§4.3) scales each replica's time-budget share — time
        # budget is the per-job "demand" here (padded slots get 0: their
        # combos are dead, so a zero budget stays trivially feasible)
        time_rhs = (np.ones(n_local) if scale_vec is None
                    else np.asarray(scale_vec, np.float64))
        q = np.concatenate([
            np.zeros(n_local),                            # epigraph rows
            time_rhs,                                     # time rows
            wl.num_workers * frac,                        # worker rows
        ])
        ineq = np.ones(q.shape[0], bool)
        data = (jnp.asarray(S, jnp.float32), jnp.asarray(member, jnp.int32),
                jnp.asarray(z, jnp.float32), jnp.zeros(n_local + 1, jnp.float32))
        structured = (None if self.space_sharing
                      else self._structured(S, member, z, n_local))
        return OperatorLP(
            c=jnp.asarray(c, jnp.float32), q=jnp.asarray(q, jnp.float32),
            l=jnp.asarray(l, jnp.float32), u=jnp.asarray(u, jnp.float32),
            ineq_mask=jnp.asarray(ineq), data=data, structured=structured)

    def build_sub(self, idx_row: np.ndarray, frac: float,
                  scale: Optional[np.ndarray] = None) -> OperatorLP:
        n_local = idx_row.shape[0]
        lut = np.full(self.n_entities + 1, n_local, np.int64)
        valid = idx_row >= 0
        lut[idx_row[valid]] = np.flatnonzero(valid)
        local_of = lambda g: lut[g]
        combos = self._combos(idx_row)
        return self._build(combos, local_of, n_local, frac, scale)

    # --- solution handling ----------------------------------------------------
    def extract(self, op: OperatorLP, x: np.ndarray, idx_row: np.ndarray):
        """Per-job normalised effective throughput rho_m (the quantity the
        paper's Fig. 3 reports the mean of)."""
        S, member, z, seg = op.data
        n_local = seg.shape[0] - 1
        C, R, _ = np.asarray(S).shape
        X = x[: C * R].reshape(C, R)
        contrib = np.einsum("crs,cr->cs", np.asarray(S), X)
        thpt = np.zeros(n_local + 1)
        np.add.at(thpt, np.asarray(member).reshape(-1), contrib.reshape(-1))
        return thpt[: idx_row.shape[0]]

    def evaluate(self, rho: np.ndarray) -> dict:
        return {
            "mean_norm_throughput": float(rho.mean()),
            "min_norm_throughput": float(rho.min()),
            "p10_norm_throughput": float(np.percentile(rho, 10)),
        }


# ---------------------------------------------------------------------------
# heuristic baseline (Gandiva-like introspective packing)
# ---------------------------------------------------------------------------

def gandiva_heuristic(wl: ClusterWorkload, space_sharing: bool = True,
                      seed: int = 0) -> np.ndarray:
    """Greedy affinity + opportunistic pair-packing, Gandiva-style.

    Each job is placed on its best-throughput type (subject to capacity,
    filling types in affinity order); when a type is oversubscribed, jobs
    time-share it equally; with space sharing, the heuristic packs pairs of
    jobs with compatible interference to reclaim time.  Returns per-job
    normalised effective throughput (same metric as GavelProblem.extract).
    """
    rng = np.random.default_rng(seed)
    n, R = wl.T.shape
    scale = 1.0 / (wl.w * wl.T.max(axis=1))
    order = rng.permutation(n)
    assign = np.zeros(n, np.int64)
    count = np.zeros(R)
    for m in order:
        prefs = np.argsort(-wl.T[m])
        # place on best type whose load (jobs per worker) is lowest relative
        load = count[prefs] / wl.num_workers[prefs]
        pick = prefs[int(np.argmin(load + np.arange(R) * 0.05))]
        assign[m] = pick
        count[pick] += wl.z[m]

    rho = np.zeros(n)
    for j in range(R):
        members = np.flatnonzero(assign == j)
        if members.size == 0:
            continue
        cap = wl.num_workers[j]
        if space_sharing and members.size > cap:
            # pack pairs (best interference first) until fits
            members_sorted = members[np.argsort(-wl.interference[members])]
            n_pairs = min(int(members.size - cap), members.size // 2)
            paired = members_sorted[: 2 * n_pairs]
            alone = members_sorted[2 * n_pairs:]
            eff_units = n_pairs + alone.size
            share = min(1.0, cap / max(eff_units, 1))
            inter = wl.interference[paired]
            rho[paired] = wl.T[paired, j] * scale[paired] * share * inter
            rho[alone] = wl.T[alone, j] * scale[alone] * share
        else:
            share = min(1.0, cap / members.size)
            rho[members] = wl.T[members, j] * scale[members] * share
    return rho
