"""The comparison that decides ``correct``.

The program's results for a sample of the points the window finished are
set beside the reference's results for the same points (``reference``),
and three numbers are read, each the worst over the sampled points:

  count_gap    the largest relative gap of a count: throughput,
               committed, the commit timelines (all and per origin), the
               async share and views (Mandator-Sporades), the latency
               sketch's total weight (sharded grids). Requests are whole,
               so only the division by the window's length rounds.
  latency_gap  the largest relative gap of a latency: median and p99,
               per origin too, the per-origin latency timeline, the
               sketch's quantiles (sharded grids: 10th to 99th
               percentile, decoded from its buckets). A batch whose rank
               lands on a bucket edge may fall in either bucket where the
               chip rounds a division differently, so the buckets are
               not compared one by one. A weighted quantile that lands
               on a neighbouring batch, where the chip rounds a division
               differently, moves by the gap between the two.
  state_gap    the share of protocol-state entries (each tick's committed
               vector clocks and commit keys, Mandator-Sporades) that
               differ at all. Absent where the grid does not return them.

NaN must meet NaN. Each number has its limit in ``limits.json``; a
missing point or key counts as a gap of 1.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List

import numpy as np

LIMITS = Path(__file__).resolve().parent / "limits.json"

COUNT_KEYS = ("throughput", "committed", "timeline", "origin_timeline",
              "async_frac", "views", "sketch.total")
LATENCY_KEYS = ("median_ms", "p99_ms", "origin_median_ms", "origin_p99_ms",
                "origin_lat_ms_timeline", "sketch.quantiles")
SKETCH_QS = (0.1, 0.5, 0.9, 0.99)
FLOAT_KEYS = COUNT_KEYS + LATENCY_KEYS
STATE_KEYS = ("cvc_all", "commit_key")


def limits() -> Dict[str, float]:
    with open(LIMITS) as fh:
        return {k: float(v) for k, v in json.load(fh).items()
                if not k.startswith("_")}


def rel_gap(a, b) -> float:
    """Largest |a - b| / max(|a|, |b|); 1 where NaNs or shapes differ."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    if a.shape != b.shape:
        return 1.0
    na, nb = np.isnan(a), np.isnan(b)
    if not np.array_equal(na, nb):
        return 1.0
    a, b = a[~na], b[~nb]
    with np.errstate(invalid="ignore"):
        same = a == b                       # equal infinities too
        d = np.abs(a - b) / np.maximum(np.abs(a), np.abs(b))
    d = np.where(same, 0.0, d)
    return float(np.max(d, initial=0.0))


def sketch_quantile(v, w, q: float) -> float:
    """Weighted quantile of a sketch's bucket centres: the first centre,
    in sorted order, whose cumulative weight share reaches q."""
    v = np.asarray(v, np.float32)
    w = np.asarray(w, np.float32)
    order = np.argsort(v, kind="stable")
    cum = np.cumsum(w[order], dtype=np.float32)
    if not cum[-1] > 0:
        return float("nan")
    cdf = cum / cum[-1]
    i = min(int(np.searchsorted(cdf, np.float32(q), side="left")),
            len(v) - 1)
    return float(v[order][i])


def _get(row: dict, key: str):
    if not key.startswith("sketch."):
        return row.get(key)
    if "sketch" not in row:
        return None
    sk = row["sketch"]
    if key == "sketch.total":
        return np.sum(np.asarray(sk["w"], np.float64))
    return [sketch_quantile(sk["v"], sk["w"], q) for q in SKETCH_QS]


def compare(program: List[dict], reference: List[dict]) -> Dict[str, dict]:
    """Per number: its value and where it peaked."""
    out = {"count_gap": {"value": 0.0, "at": None},
           "latency_gap": {"value": 0.0, "at": None}}
    bad = total = 0
    has_state = False
    for i, (p, r) in enumerate(zip(program, reference)):
        for name, keys in (("count_gap", COUNT_KEYS),
                           ("latency_gap", LATENCY_KEYS)):
            for k in keys:
                want = _get(r, k)
                if want is None:
                    continue
                got = None if p is None else _get(p, k)
                g = 1.0 if got is None else rel_gap(got, want)
                if g > out[name]["value"]:
                    out[name] = {"value": g, "at": f"point {i} {k}"}
        if all(k in r for k in STATE_KEYS):
            has_state = True
            for k in STATE_KEYS:
                a = None if p is None else p.get(k)
                b = np.asarray(r[k])
                total += b.size
                bad += (b.size if a is None or np.shape(a) != b.shape
                        else int(np.sum(np.asarray(a) != b)))
    if len(program) != len(reference):
        out["count_gap"] = {"value": 1.0, "at": "point count differs"}
    if has_state:
        out["state_gap"] = {"value": bad / max(total, 1),
                            "at": f"{bad} of {total} entries"}
    return out
