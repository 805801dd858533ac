"""The harness refuses to measure without a chip, and finds a
configuration, a traffic mix and a per-layer metric added as new files
with new entries, with no existing file edited."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from lib import device, loops, spec

BENCH = spec.BENCH_DIR
ROOT = spec.ROOT


def _run(cwd, *args, env_extra=None, timeout=240):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=timeout)


def _has_result(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    if not lines:
        return False
    try:
        return "correct" in json.loads(lines[-1])
    except ValueError:
        return False


def test_measurement_refuses_a_cpu():
    p = _run(ROOT, "--workload", "moe_ep32_onecheck_open", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert p.returncode == 3, p.stderr
    assert "no TPU" in p.stderr
    assert not _has_result(p.stdout)


def test_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, "--workload", "moe_ep32_onecheck_open", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert not _has_result(p.stdout)


def test_peaks_table():
    v5e = device.peaks("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9
    with pytest.raises(device.NoChip):
        device.peaks("cpu")


def _tree_digest(root) -> dict:
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_new_cell_is_found_from_new_files(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _tree_digest(tmp_path / "bench")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    b = tmp_path / "bench"
    cfg = json.loads((b / "configs" / "moe_dsv3_ep32_onecheck.json").read_text())
    cfg.update(name="moe_dsv3_ep144", devices=144,
               expert_slots_per_device=2)
    (b / "configs" / "moe_dsv3_ep144.json").write_text(json.dumps(cfg))
    (b / "traffic" / "fleet_slow.json").write_text(json.dumps(dict(
        rate_per_s=2.0, drift=0.1, warm_groups=[1, 2], trace_after_s=1,
        trace_span_s=1)))
    (b / "limits" / "moe_ep144_fleet_slow.json").write_text(json.dumps(
        {"limits": {"bad": 0, "primal": 1, "gap": 1, "objective": 1,
                    "served": 1}}))
    (b / "metrics" / "plan_misses.open.py").write_text(
        "def read(ctx):\n"
        "    return sum(r['plan_cache'] == 'miss' for r in ctx.records)\n")
    bench["configs"].append(dict(name="moe_dsv3_ep144", source="x",
                                 file="bench/configs/moe_dsv3_ep144.json",
                                 reduced=[], why="a test"))
    bench["workloads"].append(dict(name="moe_ep144_fleet_slow",
                                   config="moe_dsv3_ep144",
                                   traffic="fleet_slow", chips=1,
                                   why="a test"))
    bench["per_layer"].append(dict(name="plan_misses.open", unit="steps",
                                   better="lower",
                                   source="program_counter",
                                   layer="service", moves="step_p50_ms",
                                   workloads=["moe_ep144_fleet_slow"]))
    for m in bench["end_to_end"]:
        if m["name"] == "step_p50_ms":
            m["workloads"].append("moe_ep144_fleet_slow")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load("moe_ep144_fleet_slow", str(tmp_path / "BENCHMARK.json"),
                     str(b))
    assert cell.config["devices"] == 144 and cell.traffic["rate_per_s"] == 2
    assert [m["name"] for m in cell.end_to_end] == [
        "setup_s", "step_p50_ms", "quality"]
    assert [m["name"] for m in cell.per_layer] == ["plan_misses.open"]
    sched = loops.open_schedule(cell.domain, cell.config, cell.traffic, 1,
                                3.0)
    assert len(sched["requests"]) == 6
    assert sched["cold"][0]["cap"].shape == (144,)
    ctx = type("Ctx", (), {"records": [{"plan_cache": "miss"}] * 2})
    assert cell.reader(cell.per_layer[0])(ctx) == 2
    after = _tree_digest(b)
    assert all(after[k] == v for k, v in before.items())
