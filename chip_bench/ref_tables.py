"""Reference lowering of a cell's scenario and workload descriptions.

A traffic file describes each network scenario and each traffic shape as
a list of primitives with their parameters (``{"kind": "TargetedDelay",
"delay_ms": 800, ...}``). The harness hands the same parameters to the
program's own primitive classes; this module turns them, independently of
the program, into per-window tables for the reference simulator:

  scenario: alive [W, n], drop [W, n, n], extra_delay [W, n, n] (ticks),
            nic_scale [W, n], and win_of_tick [T]
  workload: rate_of [W, n] (multiplier of each origin's uniform share),
            and win_of_tick [T]

Windows are the maximal intervals between every primitive's tick edges.
A boundary at ``s`` seconds is the first tick at or after it, computed in
float32 (the simulator's time precision).
"""
from __future__ import annotations

import math

import numpy as np


def tick_of(seconds: float, tick_ms: float, n_ticks: int) -> int:
    if not math.isfinite(seconds):
        return n_ticks
    ticks = np.float32(seconds * 1000.0 / tick_ms)
    return min(n_ticks, max(0, int(np.ceil(ticks))))


def _targets(sel, n: int) -> np.ndarray:
    mask = np.zeros((n,), bool)
    if isinstance(sel, str):
        if sel == "all":
            mask[:] = True
        elif sel == "leader":
            mask[0] = True
        elif sel == "minority":
            mask[: (n - 1) // 2] = True
        else:
            raise ValueError(f"unknown target selector {sel!r}")
    else:
        mask[np.asarray(list(sel), np.int64)] = True
    return mask


def _span(p: dict, tick_ms: float, n_ticks: int):
    return (tick_of(p.get("start_s", 0.0), tick_ms, n_ticks),
            tick_of(p.get("end_s", math.inf), tick_ms, n_ticks))


def _windows(edges, n_ticks: int):
    starts = np.array(sorted({0} | {int(e) for e in edges
                                    if 0 <= e < n_ticks}), np.int64)
    win_of_tick = (np.searchsorted(starts, np.arange(n_ticks), side="right")
                   - 1).astype(np.int32)
    return starts, win_of_tick


# ---------------------------------------------------------------- scenarios

def _scenario_edges(p: dict, tick_ms: float, n_ticks: int):
    kind = p["kind"]
    if kind == "TargetedDelay":
        t0, t1 = _span(p, tick_ms, n_ticks)
        if p.get("repick_s") is None:
            return [t0, t1]
        step = max(1, int(p["repick_s"] * 1000.0 / tick_ms))
        return list(range(t0, t1, step)) + [t1]
    if kind in ("Crash", "Partition", "BandwidthThrottle"):
        return list(_span(p, tick_ms, n_ticks))
    raise ValueError(f"the reference has no scenario primitive {kind!r}")


def _paint_scenario(p: dict, tick_ms: float, n_ticks: int,
                    starts: np.ndarray, tab: dict) -> None:
    n = tab["alive"].shape[1]
    kind = p["kind"]
    t0, t1 = _span(p, tick_ms, n_ticks)
    covered = (starts >= t0) & (starts < t1)
    if kind == "TargetedDelay":
        delay = np.float32(p.get("delay_ms", 800.0) / tick_ms)
        rows = np.flatnonzero(covered)
        if p.get("targets") == "random-minority":
            step = max(1, int(p["repick_s"] * 1000.0 / tick_ms))
            rng = np.random.RandomState(p.get("seed", 7))
            f = (n - 1) // 2
            picks = []
            for w in rows:
                k = (int(starts[w]) - t0) // step
                while len(picks) <= k:
                    picks.append(rng.choice(n, size=f, replace=False))
                att = np.zeros((n,), bool)
                att[picks[k]] = True
                tab["extra_delay"][w] += (att[:, None] | att[None, :]) * delay
        else:
            att = _targets(p.get("targets", "minority"), n)
            tab["extra_delay"][rows] += ((att[:, None] | att[None, :])
                                         * delay)[None]
    elif kind == "Crash":
        tab["alive"][np.ix_(covered, _targets(p.get("targets", "leader"),
                                              n))] = False
    elif kind == "Partition":
        member = np.full((n,), -1, np.int64)
        for gi, g in enumerate(p["groups"]):
            member[np.asarray(list(g), np.int64)] = gi
        cut = ((member[:, None] >= 0) & (member[None, :] >= 0)
               & (member[:, None] != member[None, :]))
        tab["drop"][covered] |= cut[None]
    elif kind == "BandwidthThrottle":
        mask = _targets(p.get("targets", "all"), n)
        tab["nic_scale"][np.ix_(covered, mask)] *= np.float32(
            p.get("scale", 0.1))


def scenario_tables(prims, n: int, tick_ms: float, n_ticks: int) -> dict:
    edges = [e for p in prims for e in _scenario_edges(p, tick_ms, n_ticks)]
    starts, win_of_tick = _windows(edges, n_ticks)
    w = len(starts)
    tab = {"alive": np.ones((w, n), bool),
           "drop": np.zeros((w, n, n), bool),
           "extra_delay": np.zeros((w, n, n), np.float32),
           "nic_scale": np.ones((w, n), np.float32)}
    for p in prims:
        _paint_scenario(p, tick_ms, n_ticks, starts, tab)
    tab["win_of_tick"] = win_of_tick
    return tab


def scenario_bounds(tab: dict):
    """(largest extra delay in ticks, smallest NIC scale) of a scenario."""
    return (float(np.max(tab["extra_delay"], initial=0.0)),
            float(np.min(tab["nic_scale"], initial=1.0)))


# ---------------------------------------------------------------- workloads

def _workload_edges(p: dict, tick_ms: float, n_ticks: int):
    kind = p["kind"]
    if kind == "PoissonOpen":
        return []
    if kind == "OnOffBurst":
        t0, t1 = _span(p, tick_ms, n_ticks)
        start, period, duty = (p.get("start_s", 0.0), p["period_s"],
                               p.get("duty", 0.5))
        out = [t0, t1]
        k = 0
        while True:
            on = tick_of(start + k * period, tick_ms, n_ticks)
            off = tick_of(start + (k + duty) * period, tick_ms, n_ticks)
            if on >= t1 and off >= t1:
                break
            out += [on, off]
            k += 1
        return [e for e in out if t0 <= e <= t1]
    raise ValueError(f"the reference has no workload primitive {kind!r}")


def _paint_workload(p: dict, tick_ms: float, n_ticks: int,
                    starts: np.ndarray, rate_of: np.ndarray) -> None:
    kind = p["kind"]
    if kind == "PoissonOpen":
        rate_of *= np.float64(p.get("scale", 1.0))
    elif kind == "OnOffBurst":
        t0, t1 = _span(p, tick_ms, n_ticks)
        mask = _targets(p.get("targets", "all"), rate_of.shape[1])
        period = max(p["period_s"] * 1000.0 / tick_ms, 1.0)
        covered = (starts >= t0) & (starts < t1)
        for w in np.flatnonzero(covered):
            nxt = starts[w + 1] if w + 1 < len(starts) else n_ticks
            phase = (((starts[w] + nxt) / 2.0 - t0) % period) / period
            s = (p.get("on_scale", 2.0) if phase < p.get("duty", 0.5)
                 else p.get("off_scale", 0.0))
            rate_of[w, mask] *= np.float64(s)


def workload_tables(prims, n: int, tick_ms: float, n_ticks: int) -> dict:
    edges = [e for p in prims for e in _workload_edges(p, tick_ms, n_ticks)]
    starts, win_of_tick = _windows(edges, n_ticks)
    rate_of = np.ones((len(starts), n), np.float64)
    for p in prims:
        _paint_workload(p, tick_ms, n_ticks, starts, rate_of)
    rate_of = rate_of.astype(np.float32)
    trivial = rate_of.shape[0] == 1 and bool(np.all(rate_of == 1.0))
    return {"rate_of": rate_of, "win_of_tick": win_of_tick,
            "trivial": trivial}
