"""The traffic is pinned: a hash of each generator's output for a fixed
seed, so that no edit of the program, and no unnoticed edit here, moves
what a cell offers."""

import hashlib

import numpy as np
import pytest

from lib import loops, spec

CELL_MOE = "moe_ep32_onecheck_open"
SEED = 3_000_000_019           # a seed may exceed 2**31


def _digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(str(a.dtype).encode() + str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


def _sched_digest(sched) -> str:
    reqs = sched["requests"]
    return _digest(np.array([d for d, _, _ in reqs]),
                   np.array([t for _, t, _ in reqs]),
                   *[i["load"] for _, _, i in reqs],
                   *[i["compute"] for _, _, i in reqs],
                   *[i[k] for i in sched["cold"]
                     for k in ("load", "mem", "current", "cap")])


@pytest.fixture(scope="module")
def moe():
    return spec.load(CELL_MOE)


def test_moe_layer_pinned(moe):
    layer = moe.domain.make_layer(256, 32, skew=1.2, overload=1.25, seed=0)
    assert _digest(*[layer[k] for k in ("load", "mem", "current", "cap",
                                        "compute")]) == "94708f246bbd19fa"


def test_moe_open_schedule_pinned(moe):
    sched = loops.open_schedule(moe.domain, moe.config, moe.traffic, SEED,
                                5.0)
    assert len(sched["requests"]) == round(moe.traffic["rate_per_s"] * 5.0)
    assert len(sched["cold"]) == moe.config["layers"]
    assert [len(g) for g in sched["groups"]] == moe.traffic["warm_groups"]
    assert _sched_digest(sched) == "7d6ea0e204c872f5"


def test_same_seed_same_traffic(moe):
    a = loops.open_schedule(moe.domain, moe.config, moe.traffic, 5, 3.0)
    b = loops.open_schedule(moe.domain, moe.config, moe.traffic, 5, 3.0)
    assert _sched_digest(a) == _sched_digest(b)


def test_seed_draws_the_work(moe):
    a = loops.open_schedule(moe.domain, moe.config, moe.traffic, 1, 3.0)
    b = loops.open_schedule(moe.domain, moe.config, moe.traffic, 2, 3.0)
    assert len(a["requests"]) == len(b["requests"])
    assert not np.array_equal(a["cold"][0]["load"], b["cold"][0]["load"])
    assert [d for d, _, _ in a["requests"]] != [d for d, _, _ in
                                                b["requests"]]
    assert not np.array_equal(a["requests"][0][2]["load"],
                              b["requests"][0][2]["load"])


def test_requests_deal_the_fleet_in_passes(moe):
    sched = loops.open_schedule(moe.domain, moe.config, moe.traffic, SEED,
                                10.0)
    L = moe.config["layers"]
    tenants = [t for _, t, _ in sched["requests"]]
    assert sorted(tenants[:L]) == list(range(L))
    dues = [d for d, _, _ in sched["requests"]]
    assert dues == sorted(dues) and 0.0 <= dues[0] and dues[-1] < 10.0
