"""The check's control: the reference computed in bfloat16 put in the
program's place.

    python3 chip_bench/control.py --workload <cell> --seed <n> [--seed ...]

For each seed it draws the points a run with that seed would sample (the
first grids' sampled points, as many as the traffic mix's
``check_points``), runs the reference over them in float32 and in
bfloat16, the nearest precision below the float32 the simulator states,
and prints the numbers ``check.compare`` reads for the bfloat16 results
against the float32 ones. Each must come out above its limit in
``limits.json`` for the check to be worth anything; the smallest reading
over the seeds is the upper reading a limit is set below.

The benchmark's own runs never run this. JAX may stay on the host CPU
(``JAX_PLATFORMS=cpu``): both sides draw the same arrivals.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def sample_points(cell, seed: int):
    """The points a run with ``seed`` samples from its first grids."""
    from chip_bench import cell as cellmod
    t = cell.traffic
    kept = []
    i = 0
    while len(kept) < t["check_points"]:
        seeds = cellmod.grid_seeds(seed, i, t["seeds_per_grid"])
        pts = cellmod.points(cell, seeds)
        kept += [pts[j] for j in cellmod.pick(seed, f"grid{i}",
                                              t["check_per_grid"], len(pts))]
        i += 1
    return kept[:t["check_points"]]


def readings(workload: str, seed: int, root: Path = ROOT) -> dict:
    import ml_dtypes
    import numpy as np

    from chip_bench import cell as cellmod
    from chip_bench import check, ref_tables, reference
    cell = cellmod.load(workload, root)
    rc = cell.ref_cfg()
    tabs = [ref_tables.scenario_tables(s["primitives"], rc["n_replicas"],
                                       rc["tick_ms"], reference.n_ticks(rc))
            for s in cell.traffic["scenarios"]]
    d = reference.horizon(rc, tabs)
    pts = sample_points(cell, seed)
    reduced = cell.mesh is not None
    ref = reference.simulate(rc, pts, d, reduced=reduced, dtype=np.float32)
    ctl = reference.simulate(rc, pts, d, reduced=reduced,
                             dtype=ml_dtypes.bfloat16)
    return {k: v["value"] for k, v in check.compare(ctl, ref).items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chip_bench import check
    lim = check.limits()
    for s in args.seed:
        t0 = time.perf_counter()
        r = readings(args.workload, s)
        fails = [k for k, v in r.items() if v > lim[k]]
        print(json.dumps({"workload": args.workload, "seed": s,
                          "control": r, "fails": fails,
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
