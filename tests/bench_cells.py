"""One-point benchmark cells, written as new files into a throwaway copy of
the benchmark, for the tests that hold the program to the plain reference
at other cluster sizes and under faults.

A cell is one protocol at ``n`` replicas under one scenario and one
traffic shape: the protocol's shipped configuration with ``n_replicas``
set and the first ``n`` regions of the nine-region table, one rate (a
fixed rate a replica), one seed, and 1 s simulated. ``run_cell`` runs it
through the benchmark's own path (``chip_bench.run.run`` on the host CPU):
one warm-up grid, one timed grid, and the reference's check of its
point.
"""
import dataclasses
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chip_bench import run  # noqa: E402
from chip_bench.tests.helpers import tiny_root  # noqa: E402
from repro.scenarios import library  # noqa: E402

SIM_S = 1.0
SEED = 2**31 + 2468
# offered load a replica: fig 9's 60k tx/s for Mandator-Sporades; 10k
# keeps Multi-Paxos under its ~40k tx/s plateau at every n
RATE_PER_REPLICA = {"mandator-sporades": 60_000, "multipaxos": 10_000}
CONFIG = {"mandator-sporades": "mandator-sporades.n9",
          "multipaxos": "multipaxos.n5"}
REGIONS_FROM = "mandator-sporades.n9"

POISSON = {"name": "poisson-open", "primitives": [{"kind": "PoissonOpen"}]}
# the tiny.matrix burst: 2.5x for 40% of every 0.25 s, then silence
ONOFF = {"name": "onoff-burst", "primitives": [
    {"kind": "OnOffBurst", "period_s": 0.25, "duty": 0.4,
     "on_scale": 2.5, "off_scale": 0.0}]}


def _primitive(p) -> dict:
    return {"kind": type(p).__name__, **dataclasses.asdict(p)}


def scenario(name: str, n: int, sim_s: float = SIM_S) -> dict:
    """A scenario of ``scenarios/library.py`` at the point's length and
    ``n`` replicas, or ``leader-crash``: the leader down for good from 0.3
    of the point."""
    if name == "leader-crash":
        prims = [{"kind": "Crash", "start_s": 0.3 * sim_s,
                  "targets": "leader"}]
    else:
        prims = [_primitive(p)
                 for p in library.get(name, sim_s, n).events]
    return {"name": name, "primitives": prims}


def bench_root(tmp: Path) -> Path:
    """A throwaway copy of ``BENCHMARK.json`` and ``chip_bench/`` in
    ``tmp``, for ``add_cell`` to write cells into."""
    return tiny_root(tmp)


def add_cell(root: Path, protocol: str, n: int, scen: dict,
             workload: dict = POISSON) -> str:
    """Write one cell's configuration and traffic as new files under
    ``root`` and add the cell to its ``BENCHMARK.json``; returns its
    name."""
    here = root / "chip_bench"
    name = f"ref.{protocol}.n{n}.{scen['name']}.{workload['name']}"
    cfg = json.loads((here / "configs" / f"{CONFIG[protocol]}.json")
                     .read_text())
    table = json.loads((here / "configs" / f"{REGIONS_FROM}.json")
                       .read_text())
    cfg["name"] = f"{name}-cfg"
    cfg["regions"] = table["regions"][:n]
    cfg["rtt_ms"] = [row[:n] for row in table["rtt_ms"][:n]]
    cfg["smr"]["n_replicas"] = n
    cfg["smr"]["sim_seconds"] = SIM_S
    traffic = {"rates": [RATE_PER_REPLICA[protocol] * n],
               "seeds_per_grid": 1, "scenarios": [scen],
               "workloads": [workload], "mesh": None,
               "check_per_grid": 1, "check_points": 1}
    (here / "configs" / f"{name}-cfg.json").write_text(json.dumps(cfg))
    (here / "traffic" / f"{name}-mix.json").write_text(json.dumps(traffic))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": name, "config": f"{name}-cfg",
                               "traffic": f"{name}-mix", "chips": 1,
                               "why": "test cell"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return name


def run_cell(root: Path, name: str) -> dict:
    return run.run(name, SEED, 0.01, False, root=root, require_chip=False)


def assert_matches_reference(out: dict, protocol: str) -> None:
    """The point is correct, no point failed, and the program agrees with
    the reference to float rounding: every protocol-state entry, every
    count and every latency."""
    assert out["correct"], out["check"]
    assert out["attempted"] == 1 and out["failed"] == 0
    check = out["check"]
    assert "horizon_gap" not in check
    assert check["count_gap"]["value"] < 1e-6, check
    assert check["latency_gap"]["value"] < 1e-6, check
    if protocol == "mandator-sporades":
        assert check["state_gap"]["value"] == 0.0, check
