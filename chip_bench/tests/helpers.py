"""A throwaway copy of the benchmark with small cells, for the tests."""
import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

TINY = {
    # cell: (config it copies, traffic)
    "tiny.ms": ("mandator-sporades.n5", {
        "rates": [150000, 450000], "seeds_per_grid": 1,
        "scenarios": [{"name": "baseline", "primitives": []}],
        "workloads": [{"name": "poisson-open",
                       "primitives": [{"kind": "PoissonOpen"}]}],
        "mesh": None, "check_per_grid": 2, "check_points": 4}),
    "tiny.mp": ("multipaxos.n5", {
        "rates": [30000, 100000], "seeds_per_grid": 1,
        "scenarios": [{"name": "baseline", "primitives": []}],
        "workloads": [{"name": "poisson-open",
                       "primitives": [{"kind": "PoissonOpen"}]}],
        "mesh": None, "check_per_grid": 2, "check_points": 4}),
    "tiny.matrix": ("mandator-sporades.n5", {
        "rates": [150000, 450000], "seeds_per_grid": 1,
        "scenarios": [{"name": "baseline", "primitives": []},
                      {"name": "paper-ddos", "primitives": [
                          {"kind": "TargetedDelay", "delay_ms": 800.0,
                           "targets": "random-minority", "repick_s": 0.25,
                           "seed": 7}]}],
        "workloads": [{"name": "poisson-open",
                       "primitives": [{"kind": "PoissonOpen"}]},
                      {"name": "onoff-burst", "primitives": [
                          {"kind": "OnOffBurst", "period_s": 0.25,
                           "duty": 0.4, "on_scale": 2.5,
                           "off_scale": 0.0}]}],
        "mesh": 4, "check_per_grid": 8, "check_points": 8}),
}


def tiny_root(tmp: Path, sim_seconds: float = 1.0) -> Path:
    """A copy of BENCHMARK.json and chip_bench/ with the tiny cells added
    as new files and new entries: nothing that is there is edited."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    shutil.copytree(ROOT / "chip_bench", tmp / "chip_bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((tmp / "BENCHMARK.json").read_text())
    for name, (config, traffic) in TINY.items():
        cfg = json.loads((tmp / "chip_bench" / "configs"
                          / f"{config}.json").read_text())
        cfg["smr"]["sim_seconds"] = sim_seconds
        cfg["name"] = f"{name}-cfg"
        (tmp / "chip_bench" / "configs" / f"{name}-cfg.json").write_text(
            json.dumps(cfg))
        (tmp / "chip_bench" / "traffic" / f"{name}-mix.json").write_text(
            json.dumps(traffic))
        bench["workloads"].append({"name": name, "config": f"{name}-cfg",
                                   "traffic": f"{name}-mix",
                                   "chips": 4 if traffic["mesh"] else 1,
                                   "why": "test cell"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp
