import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""§Perf hillclimb harness: measure roofline terms for (arch, shape) under
named optimization flags, printing before/after-comparable lines.

    python -m repro.launch.perf --arch gemma3_4b --shape train_4k \
        --flags sp_residual,bf16_barrier
"""

import argparse
import json

import numpy as np

from ..configs import get_config
from ..launch import specs as sp
from ..launch import shardings as sh
from ..launch.dryrun import probe_costs
from ..launch.mesh import make_auto_mesh, make_production_mesh
from ..launch.roofline import HBM_BW, ICI_BW, PEAK_FLOPS, fmt_s


def measure(arch: str, shape: str, flags: dict, mesh_shape=None) -> dict:
    import jax
    cfg = get_config(arch)
    cell = sp.SHAPES[shape]
    if mesh_shape is not None:
        mesh = make_auto_mesh(mesh_shape, ("data", "model"))
    else:
        mesh = make_production_mesh(multi_pod=False)
    n_dp = int(np.prod([mesh.shape[a] for a in sh.dp_axes(mesh)]))
    p = probe_costs(cfg, cell, mesh, n_dp, flags=flags)
    out = {
        "arch": arch, "shape": shape, "flags": flags,
        "flops_per_device": p["flops_per_device"],
        "bytes_per_device": p["bytes_per_device"],
        "collective_bytes_per_device": p["collective_bytes_per_device"],
        "compute_s": p["flops_per_device"] / PEAK_FLOPS,
        "memory_s": p["bytes_per_device"] / HBM_BW,
        "collective_s": p["collective_bytes_per_device"] / ICI_BW,
    }
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--flags", default="")
    ap.add_argument("--tag", default=None)
    ap.add_argument("--mesh-shape", default=None,
                    help="e.g. 32x8 (default: production 16x16)")
    args = ap.parse_args()
    flags = {}
    for f in args.flags.split(","):
        if not f:
            continue
        if "=" in f:
            k, v = f.split("=")
            try:
                flags[k] = float(v)
            except ValueError:
                flags[k] = v in ("1", "true", "True")
        else:
            flags[f] = True
    mesh_shape = (tuple(int(x) for x in args.mesh_shape.split("x"))
                  if args.mesh_shape else None)
    r = measure(args.arch, args.shape, flags, mesh_shape=mesh_shape)
    tag = args.tag or (",".join(sorted(flags)) or "baseline")
    print(f"[perf] {args.arch}/{args.shape} [{tag}] "
          f"compute={fmt_s(r['compute_s'])} memory={fmt_s(r['memory_s'])} "
          f"collective={fmt_s(r['collective_s'])} "
          f"(coll_bytes={r['collective_bytes_per_device']:.3e})")
    out_dir = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "experiments", "perf")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir,
                           f"{args.arch}__{args.shape}__{tag}.json"),
              "w") as f:
        json.dump(r, f, indent=1)


if __name__ == "__main__":
    main()
