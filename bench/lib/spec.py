"""Finds what ``BENCHMARK.json`` names, by name, in files of their own.

    configs/<config>.json        a configuration: its domain, sizes, source
    traffic/<traffic>.json       a traffic mix: parameters the loops read
    domains/<domain>.py          a domain's generator and plain reference
    metrics/<metric>.py          a per-layer metric's reader, ``read(ctx)``
    limits/<workload>.json       the limits of the cell's compared numbers

A later cell, mix, configuration or metric is a new file and a new entry;
no file here needs an edit for it.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(Exception):
    """What ``BENCHMARK.json`` names cannot be found or is malformed."""


def _json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise SpecError(f"cannot read {path}: {e}") from e


def _module(path: str, name: str):
    if not os.path.isfile(path):
        raise SpecError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    def __init__(self, bench: dict, name: str, bench_dir: str = BENCH_DIR):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SpecError(f"no workload {name!r}; known: {sorted(cells)}")
        self.workload = cells[name]
        self.name = name
        self.chips = int(self.workload["chips"])
        configs = {c["name"]: c for c in bench["configs"]}
        entry = configs[self.workload["config"]]
        self.config = _json(os.path.join(bench_dir, "configs",
                                         f"{entry['name']}.json"))
        self.traffic = _json(os.path.join(
            bench_dir, "traffic", f"{self.workload['traffic']}.json"))
        self.limits = _json(os.path.join(bench_dir, "limits",
                                         f"{name}.json"))["limits"]
        self.domain = _module(
            os.path.join(bench_dir, "domains",
                         f"{self.config['domain']}.py"),
            f"bench_domain_{self.config['domain']}")
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"] if self._reports(m)]
        self.bench_dir = bench_dir

    def _reports(self, metric: dict) -> bool:
        if "workloads" in metric:
            return self.name in metric["workloads"]
        return any(m["name"] == metric["moves"] for m in self.end_to_end)

    def reader(self, metric: dict):
        """The ``read(ctx)`` of a per-layer metric."""
        name = metric["name"]
        mod = _module(os.path.join(self.bench_dir, "metrics", f"{name}.py"),
                      "bench_metric_" + name.replace(".", "_"))
        return mod.read


def load(name: str, bench_path: str = os.path.join(ROOT, "BENCHMARK.json"),
         bench_dir: str = BENCH_DIR) -> Cell:
    return Cell(_json(bench_path), name, bench_dir)
