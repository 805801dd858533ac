"""The trace reduction on a small trace recorded on a TPU v5e: three calls
of a jitted 512x512 matmul-tanh-matmul, each under ``bench.step`` and
followed by a 2 ms ``bench.wait``, all under ``bench.traced``."""

import os

import numpy as np
import pytest

from lib import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "v5e_small.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce_trace(DATA)


def test_window_is_the_traced_span(reduced):
    assert reduced["window_s"] == pytest.approx(0.01048572)


def test_busy_is_the_union_of_device_ops(reduced):
    # the fusions of the two calls whose device ops fall in the span
    assert reduced["busy_s"] == pytest.approx(7.914e-06)
    assert 0 < reduced["busy_s"] < reduced["window_s"]


def test_breakdown(reduced):
    ops = dict(reduced["device_ops"])
    assert max(ops, key=ops.get) == "fusion"
    idle = dict(reduced["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"])
    assert max(idle, key=idle.get).startswith("bench.wait")
    assert any(k.startswith("bench.step") for k in idle)
    assert len(reduced["device_ops"]) <= trace.TOP


def test_union():
    iv = np.array([[0, 2], [1, 3], [5, 6], [5.5, 5.7]], float)
    assert trace._union(iv).tolist() == [[0, 3], [5, 6]]


def test_no_trace_is_an_error(tmp_path):
    with pytest.raises(FileNotFoundError):
        trace.latest_xplane(str(tmp_path))
