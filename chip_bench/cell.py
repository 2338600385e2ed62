"""Find a cell's files by name and turn them into the program's inputs.

``BENCHMARK.json`` names each cell (``workloads``), its configuration and
its traffic mix. A configuration is ``configs/<config>.json``: protocol,
the ``SMRConfig`` fields it sets, the RTT table of its regions, source,
``reduced`` and ``assumed``. A traffic mix is ``traffic/<traffic>.json``:
the rates, seeds per grid, scenarios and workloads (each a list of
primitives with their parameters), the mesh size when the grid is
sharded, and how many finished points the check samples. Nothing here is
specific to one cell.
"""
from __future__ import annotations

import hashlib
import json
import random
from dataclasses import dataclass
from pathlib import Path
from typing import List, Optional

ROOT = Path(__file__).resolve().parents[1]


def _load(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def _tupled(x):
    """JSON lists -> tuples, so primitives stay hashable."""
    if isinstance(x, list):
        return tuple(_tupled(v) for v in x)
    return x


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict

    @property
    def protocol(self) -> str:
        return self.config["protocol"]

    @property
    def mesh(self) -> Optional[int]:
        return self.traffic.get("mesh")

    @property
    def points_per_grid(self) -> int:
        t = self.traffic
        return (len(t["rates"]) * t["seeds_per_grid"] * len(t["scenarios"])
                * len(t["workloads"]))

    def ref_cfg(self) -> dict:
        """The settings the reference reads: the SMR fields, the RTT
        table and the protocol."""
        return {**self.config["smr"], "rtt_ms": self.config["rtt_ms"],
                "protocol": self.protocol}


def load(name: str, root: Path = ROOT) -> Cell:
    bench = _load(root / "BENCHMARK.json")
    entry = {w["name"]: w for w in bench["workloads"]}.get(name)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; known: "
                         + ", ".join(w["name"] for w in bench["workloads"]))
    here = root / "chip_bench"
    return Cell(name=name, chips=int(entry["chips"]),
                config=_load(here / "configs" / f"{entry['config']}.json"),
                traffic=_load(here / "traffic" / f"{entry['traffic']}.json"))


def grid_seeds(seed: int, grid: int, count: int) -> List[int]:
    """Seeds of grid ``grid`` of a run started with ``--seed seed``: a
    hash of both, in [0, 2**31) (the program keys its PRNG with int32)."""
    out = []
    for j in range(count):
        h = hashlib.sha256(f"{seed}:{grid}:{j}".encode()).digest()
        out.append(int.from_bytes(h[:4], "little") & 0x7FFFFFFF)
    return out


def pick(seed: int, tag: str, k: int, n: int) -> List[int]:
    """k distinct indices of range(n), drawn from the seed and a tag: the
    points of a grid, and of a run, that the check compares."""
    h = hashlib.sha256(f"{seed}:{tag}".encode()).digest()
    rng = random.Random(int.from_bytes(h[:8], "little"))
    return sorted(rng.sample(range(n), min(k, n)))


def points(cell: Cell, seeds: List[int]) -> List[dict]:
    """The grid's points in the program's order (rate-major, then seed,
    scenario, workload) with everything the reference needs."""
    t = cell.traffic
    return [{"rate": float(r), "seed": int(s), "scenario": sc["primitives"],
             "workload": wl["primitives"]}
            for r in t["rates"] for s in seeds
            for sc in t["scenarios"] for wl in t["workloads"]]


# ------------------------------------------------- the program's objects

def smr_config(cell: Cell):
    """The program's ``SMRConfig`` with every field the file sets; refuses
    a file whose RTT table the program does not use."""
    import numpy as np

    from repro.configs.smr import SMRConfig
    cfg = SMRConfig(**cell.config["smr"])
    n = cfg.n_replicas
    want = np.asarray(cell.config["rtt_ms"], np.float64)[:n, :n] / 2.0
    if not np.array_equal(cfg.delays_ms(), want):
        raise SystemExit(f"{cell.name}: the program's one-way delays differ "
                         f"from the RTT table in the configuration file")
    return cfg


def _primitive(module, p: dict):
    kw = {k: _tupled(v) for k, v in p.items() if k != "kind"}
    return getattr(module, p["kind"])(**kw)


def sweep_spec(cell: Cell, seeds: List[int], rates=None):
    from repro import scenarios as sc
    from repro import workloads as wl
    from repro.core.experiment import SweepSpec
    t = cell.traffic
    return SweepSpec(
        rates=tuple(float(r) for r in (rates or t["rates"])),
        seeds=tuple(seeds),
        scenarios=tuple(sc.Scenario(s["name"], tuple(
            _primitive(sc, p) for p in s["primitives"]))
            for s in t["scenarios"]),
        workloads=tuple(wl.Workload(w["name"], tuple(
            _primitive(wl, p) for p in w["primitives"]))
            for w in t["workloads"]))
