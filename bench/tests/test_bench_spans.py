"""The five readers of the program's span log, fed a synthetic log: each
reads the right mean, counts only the requests whose ``pop.step`` began
in ``[t0, stretch[0])``, and reads nothing without a stretch, with a ring
that dropped spans of the window, or from a program without the log."""

import sys
import types

import pytest
from repro import obs

from lib import spec

MS = 1_000_000
T0 = 100.0                     # window start, perf_counter seconds
STRETCH = (120.0, 120.5)
READERS = ["start_wait_ms.open", "prep_cpu_ms.open", "prep_wait_ms.open",
           "queue_ms.open", "finish_ms.open"]


def _request(rid: int, start_s: float, scale: int, first_id: int) -> list:
    """Spans of one request starting at ``start_s``; every duration is a
    multiple of ``scale`` ms."""
    t = int(start_s * 1e9)
    ids = iter(range(first_id, first_id + 20))
    root = next(ids)

    def sp(name, t0_ms, dur_ms, cpu_ms=None, parent=root):
        return obs.Span(name, next(ids), parent, rid,
                        t + t0_ms * scale * MS,
                        t + (t0_ms + dur_ms) * scale * MS,
                        None if cpu_ms is None else cpu_ms * scale * MS, {})

    prep = sp("pop.prepare", 3, 10, cpu_ms=4)
    disp = sp("pop.dispatch", 14, 8, cpu_ms=1)
    return [
        sp("pop.pool_wait", 0, 2),
        sp("pop.lock_wait", 2, 1),
        sp("pop.plan", 3, 1, parent=prep.id),
        sp("pop.build", 4, 6, parent=prep.id),
        prep,
        sp("pop.prepare", 13, 1, cpu_ms=1),      # a second, as on a retry
        sp("pop.queue", 14, 5, parent=disp.id),
        disp,
        sp("pop.finish", 22, 3, cpu_ms=3),
        sp("pop.finish", 25, 1, cpu_ms=1),
        obs.Span("pop.step", root, None, rid, t, t + 26 * scale * MS, None,
                 {}),
    ]


# per request of scale s: start wait 3s, prepare cpu 5s, prepare wall 11s
# (so wait 6s), queue 5s, finish 4s; the window's requests have s = 1, 3
EXPECTED = {"start_wait_ms.open": 6.0, "prep_cpu_ms.open": 10.0,
            "prep_wait_ms.open": 12.0, "queue_ms.open": 10.0,
            "finish_ms.open": 8.0}


def _log():
    return (_request(1, T0 - 0.5, 100, 100)          # began before t0
            + _request(2, T0 + 1.0, 1, 200)
            + _request(3, T0 + 5.0, 3, 300)
            + _request(4, STRETCH[0] + 0.1, 100, 400))   # in the stretch


@pytest.fixture(scope="module")
def cell():
    return spec.load("moe_ep32_onecheck_open")


def _ctx(stretch=STRETCH):
    return types.SimpleNamespace(t0=T0, stretch=stretch)


def _reader(cell, name):
    return cell.reader(next(m for m in cell.per_layer if m["name"] == name))


@pytest.mark.parametrize("name", READERS)
def test_reads_the_window_mean(cell, monkeypatch, name):
    monkeypatch.setattr(obs, "spans", lambda: (_log(), False))
    assert _reader(cell, name)(_ctx()) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", READERS)
def test_a_wrapped_ring_counts_only_while_the_window_is_whole(
        cell, monkeypatch, name):
    log = sorted(_log(), key=lambda s: s.t1_ns)
    monkeypatch.setattr(obs, "spans", lambda: (log, True))
    assert _reader(cell, name)(_ctx()) == pytest.approx(EXPECTED[name])
    # the spans of request 1, which ended before t0, are gone, and one of
    # request 2 with them
    kept = [s for s in log if s.rid != 1][1:]
    monkeypatch.setattr(obs, "spans", lambda: (kept, True))
    assert _reader(cell, name)(_ctx()) is None


@pytest.mark.parametrize("name", READERS)
def test_nothing_to_read(cell, monkeypatch, name):
    monkeypatch.setattr(obs, "spans", lambda: (_log(), False))
    assert _reader(cell, name)(_ctx(stretch=None)) is None
    outside = [s for s in _log() if s.rid in (1, 4)]
    monkeypatch.setattr(obs, "spans", lambda: (outside, False))
    assert _reader(cell, name)(_ctx()) is None
    # a program without the span log (the parent of this benchmark's
    # readers): the import fails and the metric is left out
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    monkeypatch.delattr(sys.modules["repro"], "obs")
    assert _reader(cell, name)(_ctx()) is None
