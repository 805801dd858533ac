"""The check that decides ``correct``, at a size a test run can hold.

Each test drives the rest of a run, with the harness's look for a chip
skipped, on a tiny copy of the cell: 10 MoE layers of 64 experts over 8
devices.  A sound run comes out correct; a run with the timed path broken
underneath comes out not correct, for each fault a one-chip cell can
have: a step that returns its state unchanged, half of a launch's lanes
left unsolved, and one entity's answer altered where the allocation is
produced.  The bfloat16 control fails too.  The limits of primal and gap
are the cell's own (``limits/``); the tiny copy reads far below them and
its control far above (CPU counts: primal 3.6e-5 and gap 1.7e-5 against
1.9e-3 and 1.7e-3 at least).  The objective's limit is the tiny copy's
own, set between its reading and the control's (1.1e-7 against 3.1e-3);
served is exact.  They test the harness, not the program.
"""

import contextlib
import io
import json
import os
import re
import shutil

import numpy as np
import pytest

import control
import run
from lib import spec

TINY = {"moe_dsv3_ep32_onecheck": dict(layers=10, experts=64, devices=8)}
TRAFFIC = {"fleet_open": dict(rate_per_s=4.0, trace_after_s=1,
                              trace_span_s=1)}
LIMITS = {"moe_ep32_onecheck_open": dict(bad=0, primal=6e-4, gap=4e-4,
                                         objective=1e-5, served=0)}
SECONDS = {"moe_ep32_onecheck_open": "3"}


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    root = tmp_path_factory.mktemp("tiny")
    b = root / "bench"
    shutil.copytree(spec.BENCH_DIR, b,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), root)
    for folder, edits in (("configs", TINY), ("traffic", TRAFFIC)):
        for name, kw in edits.items():
            p = b / folder / f"{name}.json"
            p.write_text(json.dumps(dict(json.loads(p.read_text()), **kw)))
    for name, lim in LIMITS.items():
        (b / "limits" / f"{name}.json").write_text(
            json.dumps({"limits": lim}))
    return str(root / "BENCHMARK.json"), str(b), str(root / "jax_cache")


def _run(tiny, workload, seed=77, with_log=False):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", SECONDS[workload], "--trace", "0"],
                      require_chip=False, bench_path=tiny[0],
                      bench_dir=tiny[1], cache_dir=tiny[2])
    assert rc == 0
    res = json.loads(out.getvalue().strip().splitlines()[-1])
    return (res, out.getvalue()) if with_log else res


CELLS = ["moe_ep32_onecheck_open"]


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(tiny, workload):
    res, out = _run(tiny, workload, with_log=True)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0
    # a step fails here only where a lane ran out of iterations
    capped, other = re.search(r"failed: (\d+) steps with a lane at the "
                              r"iteration cap, (\d+) not ok", out).groups()
    assert int(other) == 0 and int(capped) == res["failed"]
    assert list(res)[-1] == "checks"


def _stale(monkeypatch):
    from repro.service import PopSession
    orig = PopSession.step

    def step(self, instance, **kw):
        self._bench_calls = getattr(self, "_bench_calls", 0) + 1
        if self._bench_calls > 2 and self.last is not None:
            return self.last
        return orig(self, instance, **kw)
    monkeypatch.setattr(PopSession, "step", step)


def _half_batch(monkeypatch):
    from repro.core import backends, pop
    orig = pop.finish_prepared

    def finish(prep, res, solve_s):
        _, wx, _ = backends.make_batch(prep.ops, prep.warm)
        k = res.x.shape[0]
        x = np.asarray(res.x).copy()
        x[k // 2:] = np.asarray(wx)[k // 2:]
        return orig(prep, res._replace(x=x), solve_s)
    monkeypatch.setattr(pop, "finish_prepared", finish)


def _altered(monkeypatch):
    from repro.service import PopSession
    orig = PopSession._wrap

    def wrap(self, *a, **kw):
        out = orig(self, *a, **kw)
        alloc = np.array(out.alloc, copy=True)
        if np.issubdtype(alloc.dtype, np.integer):
            alloc[0] = (alloc[0] + 1) % (alloc.max() + 1)
        else:
            alloc[0] += 0.01
        out.alloc = alloc
        return out
    monkeypatch.setattr(PopSession, "_wrap", wrap)


@pytest.mark.parametrize("fault", [_stale, _half_batch, _altered],
                         ids=["state_unchanged", "half_batch_left_out",
                              "answer_altered"])
@pytest.mark.parametrize("workload", CELLS)
def test_fault_is_not_correct(tiny, monkeypatch, workload, fault):
    fault(monkeypatch)
    res = _run(tiny, workload)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(tiny, workload):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        verdicts = control.main(["--workload", workload, "--seeds", "1", "2",
                                 "3", "--seconds", "4"],
                                bench_path=tiny[0], bench_dir=tiny[1])
    assert verdicts == [False, False, False], out.getvalue()
