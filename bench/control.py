#!/usr/bin/env python3
"""The control of ``correct``: the plain reference put in the program's
place, computed in bfloat16, on the instances a cell's window checks.

    python3 bench/control.py --workload <name> --seeds 1 2 3 [--seconds 51]

For each seed it answers as many of the window's requests as a run
checks, drawn from the seed, as ``lib/check.py`` describes the control,
and prints the compared numbers beside the cell's limits.  A control that
stays within every limit shows a check that cannot tell bfloat16 from the
program's float32.  It needs no chip: the benchmark's own runs never run
it.
"""

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import numpy as np  # noqa: E402

from lib import check, loops, spec  # noqa: E402


def instances(cell, seed: int, seconds: float) -> list:
    reqs = loops.open_schedule(cell.domain, cell.config, cell.traffic, seed,
                               seconds)["requests"]
    rng = np.random.default_rng([seed, 1])
    pick = rng.choice(len(reqs), min(check.SAMPLE + 1, len(reqs)),
                      replace=False)
    return [reqs[j][2] for j in sorted(pick)]


def main(argv=None, bench_path=os.path.join(spec.ROOT, "BENCHMARK.json"),
         bench_dir=BENCH) -> list:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args(argv)
    cell = spec.load(args.workload, bench_path, bench_dir)
    out = []
    for seed in args.seeds:
        checked = [(inst, check.control_answer(cell.domain, cell.config,
                                               inst))
                   for inst in instances(cell, seed, args.seconds)]
        values = check.numbers(cell.domain, cell.config, checked)
        correct, checks = check.verdict(values, cell.limits)
        out.append(correct)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_correct": correct, "checks": checks}),
              flush=True)
    return out


if __name__ == "__main__":
    main()
