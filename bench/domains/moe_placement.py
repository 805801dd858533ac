"""MoE expert placement over an expert-parallel group.

What the benchmark needs of this domain, kept here so that no edit to the
program can move it: the layer generator (a copy of the program's
``make_placement_instance``), the gate-load drift between rebalances, the
conversion into the program's instance type, the quality the benchmark
reads, and the plain reference that decides ``correct``.

The reference builds each lane's sub-LP from the instance and the lane's
experts with ``scipy.sparse`` and solves it with HiGHS in float64; it
imports nothing of the program.  Experts e are placed on devices d:

    maximise  sum_{e,d} (load_e - lam * mem_e * [d != current_e]) x_{e,d}
    s.t.      sum_e load_e x_{e,d} <= compute_d / k      every device d
              sum_e mem_e  x_{e,d} <= cap_d / k          every device d
              sum_d x_{e,d} <= 1                         every expert e
              0 <= x <= 1

with x laid out expert-major over the lane's slots.  The served placement
rounds the coalesced distribution: each expert to its best device, kept
where it is when that is within 1e-3 of the best or nothing is served,
then a greedy pass sheds experts from devices over their memory, and a
second moves load from saturated to starved devices while that raises the
load served.
"""

from __future__ import annotations

import numpy as np
import scipy.sparse as sparse
from scipy.optimize import linprog


def make_layer(n_experts: int, n_devices: int, *, skew: float,
               overload: float, seed) -> dict:
    """One MoE layer's experts: a copy of ``make_placement_instance``
    (same draws in the same order)."""
    rng = np.random.default_rng(seed)
    load = np.minimum(rng.zipf(skew + 1.0, n_experts), 50.0).astype(np.float64)
    load += rng.uniform(0, 1, n_experts)
    mem = rng.uniform(0.8, 1.2, n_experts)
    current = rng.permutation(n_experts) % n_devices
    cap = np.full(n_devices, 2.0 * mem.sum() / n_devices)
    compute = np.full(n_devices, load.sum() / overload / n_devices)
    return dict(load=load, mem=mem, current=current, cap=cap,
                compute=compute)


def fleet(cfg: dict, seed: int) -> list:
    """The configuration's layers, layer l drawn from the seed sequence
    ``[seed, l]``, with memory for ``expert_slots_per_device`` experts of
    mean size on every device."""
    out = []
    for layer in range(int(cfg["layers"])):
        inst = make_layer(int(cfg["experts"]), int(cfg["devices"]),
                          skew=float(cfg["zipf_skew"]),
                          overload=float(cfg["overload"]),
                          seed=[seed, layer])
        inst["cap"] = np.full(int(cfg["devices"]),
                              float(cfg["expert_slots_per_device"])
                              * inst["mem"].mean())
        inst["move_penalty"] = float(cfg["move_penalty"])
        out.append(inst)
    return out


def evolve(cfg: dict, traffic: dict, inst: dict, rng) -> dict:
    """The layer's next rebalance request: every expert's gate load times
    lognormal(0, ``drift``), compute re-sized to the routed load over the
    overload."""
    load = inst["load"] * rng.lognormal(0.0, float(traffic["drift"]),
                                        inst["load"].shape[0])
    out = dict(inst, load=load)
    out["compute"] = np.full(inst["compute"].shape[0],
                             load.sum() / float(cfg["overload"])
                             / inst["compute"].shape[0])
    return out


def to_program(cfg: dict, inst: dict):
    from repro.domains.moe_placement import MoEPlacementInstance
    return MoEPlacementInstance(
        load=inst["load"], mem=inst["mem"], current=inst["current"],
        cap=inst["cap"], compute=inst["compute"],
        move_penalty=inst["move_penalty"])


def quality(inst: dict, alloc: np.ndarray) -> float:
    """Share of the routed gate load that the placement serves: each
    device serves its experts' load up to its compute."""
    D = inst["cap"].shape[0]
    load = np.bincount(np.asarray(alloc, np.int64), inst["load"],
                       minlength=D)
    return float(np.minimum(load, inst["compute"]).sum()
                 / inst["load"].sum())


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

class LaneLP:
    """One lane's sub-LP as plain arrays: minimise ``c @ x`` subject to
    ``A @ x <= b`` and ``lo <= x <= hi``."""

    def __init__(self, inst: dict, slots: np.ndarray, k: int):
        slots = np.asarray(slots, np.int64)
        n = slots.shape[0]
        D = inst["cap"].shape[0]
        live = slots >= 0
        g = np.maximum(slots, 0)
        load = np.where(live, inst["load"][g], 0.0)
        mem = np.where(live, inst["mem"][g], 0.0)
        pen = inst["move_penalty"] * mem
        value = np.repeat((load - pen)[:, None], D, axis=1)
        here = np.flatnonzero(live)
        value[here, inst["current"][g[here]]] += pen[here]
        value[~live] = 0.0
        var = np.arange(n * D).reshape(n, D)
        dev = np.broadcast_to(np.arange(D), (n, D))
        rows = np.concatenate([dev.ravel(), D + dev.ravel(),
                               2 * D + np.repeat(np.arange(n), D)])
        cols = np.concatenate([var.ravel()] * 3)
        vals = np.concatenate([np.repeat(load, D), np.repeat(mem, D),
                               np.ones(n * D)])
        self.A = sparse.csr_matrix((vals, (rows, cols)),
                                   shape=(2 * D + n, n * D))
        self.b = np.concatenate([inst["compute"] / k, inst["cap"] / k,
                                 live.astype(np.float64)])
        self.c = -value.ravel()
        self.lo = np.zeros(n * D)
        self.hi = np.repeat(live.astype(np.float64), D)
        self.slots = slots
        self.D = D
        self.n_var = n * D

    def optimum(self) -> float:
        res = linprog(self.c, A_ub=self.A, b_ub=self.b,
                      bounds=np.stack([self.lo, self.hi], axis=1),
                      method="highs")
        if res.status != 0:
            raise RuntimeError(f"HiGHS: {res.message}")
        return float(res.fun)

    def served(self, x: np.ndarray) -> np.ndarray:
        """Each slot's distribution over the devices."""
        return np.asarray(x)[: self.n_var].reshape(-1, self.D)


def round_placement(inst: dict, r: np.ndarray) -> np.ndarray:
    """The served placement from the coalesced [experts, devices]
    distribution, as the module docstring states the rule."""
    load_e, mem_e = inst["load"], inst["mem"]
    cap, compute, current = inst["cap"], inst["compute"], inst["current"]
    E, D = r.shape
    rows = np.arange(E)
    pick = r.argmax(axis=1)
    best = r[rows, pick]
    stay = (r[rows, current] >= best - 1e-3) | (best < 1e-6)
    pick = np.where(stay, current, pick)
    load = np.zeros(D)
    mem = np.zeros(D)
    np.add.at(load, pick, load_e)
    np.add.at(mem, pick, mem_e)

    def move(e, src, dst):
        pick[e] = dst
        load[src] -= load_e[e]
        load[dst] += load_e[e]
        mem[src] -= mem_e[e]
        mem[dst] += mem_e[e]

    for _ in range(2 * E):                 # memory: shed to the emptiest
        src = int(np.argmax(mem - cap))
        if mem[src] <= cap[src]:
            break
        on = np.flatnonzero(pick == src)
        if on.size == 0:
            break
        dst = int(np.argmin(mem / cap))
        fits = mem_e[on] <= cap[dst] - mem[dst]
        if dst == src or not fits.any():
            break
        move(on[np.flatnonzero(fits)[0]], src, dst)
    for _ in range(4 * E):                 # load: saturated to starved
        surplus = load - compute
        src = int(np.argmax(surplus))
        dst = int(np.argmin(surplus))
        if surplus[src] <= 0 or surplus[dst] >= 0:
            break
        on = np.flatnonzero(pick == src)
        if on.size == 0:
            break
        gain = (np.minimum(load_e[on], -surplus[dst])
                - np.maximum(load_e[on] - surplus[src], 0.0))
        gain = np.where(mem[dst] + mem_e[on] <= cap[dst], gain, -np.inf)
        i = int(np.argmax(gain))
        if gain[i] <= 1e-9:
            break
        move(on[i], src, dst)
    return pick


# ---------------------------------------------------------------------------
# what the check in lib/check.py asks of a domain
# ---------------------------------------------------------------------------

def n_entities(inst: dict) -> int:
    return inst["load"].shape[0]


def scores(inst: dict) -> np.ndarray:
    return inst["load"]


def lane_lp(cfg: dict, inst: dict, slots: np.ndarray, k: int) -> LaneLP:
    return LaneLP(inst, slots, k)


def serve(inst: dict, lanes: np.ndarray, lps, xs, round_to=None):
    """The placement rounded from the lanes' distributions, held in float32
    as the program holds them; with ``round_to``, rounded by it first."""
    r = np.zeros((n_entities(inst), inst["cap"].shape[0]), np.float32)
    for slots, lp, x in zip(lanes, lps, xs):
        x = np.asarray(x)[: lp.n_var]
        if round_to is not None:
            x = round_to(x)
        live = slots >= 0
        r[slots[live]] = lp.served(x.astype(np.float32))[live]
    return round_placement(inst, r)


def served_error(ref: np.ndarray, alloc) -> float:
    """The number of experts placed elsewhere than the reference places
    them."""
    return float(np.count_nonzero(np.asarray(alloc) != ref))
