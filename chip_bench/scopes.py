"""Where a point's device time and a grid's host time go, layer by layer,
read from the program's own names on the profiler's clock.

    python3 chip_bench/scopes.py --workload <cell> --seed <n> [--grids 3] \
        [--keep DIR]

The program names its layers itself. A ``jax.named_scope`` on each tick
layer and on the metric extraction (``LAYERS``) survives into every
compiled instruction's ``op_name`` metadata, and four host spans around
each grid (``experiment.lower``, ``.enqueue``, ``.readback``,
``.decode``) are ``jax.profiler.TraceAnnotation``s that
``experiment.timing_stats()`` also counts. A TPU trace's operations carry
no ``op_name`` (their only stats are times), so ``hlo_op_scopes`` reads
it from the compiled module's HLO text, by the instruction name that
starts each trace event's name.

``scope_partition`` splits each device's busy time into the layers plus
``unscoped`` (the scan's own plumbing): each instant goes to the deepest
layer among the operations running then, so a tick layer's part is its
self time outside the layers nested in it, and the parts sum to the busy
time. ``idle_gaps`` names each idle gap by the innermost host span that
holds its midpoint.

The command runs one cell as ``run.py`` does: set-up and a warm-up grid,
``--grids`` untimed-by-profiler grids whose host spans give the
milliseconds a point of each span, then one traced probe (one point of a
one-chip cell, traced whole; the first ``SAMPLE_S`` seconds of a sharded
grid). It prints the split as the last line of standard output, one JSON
object. ``--keep`` also writes the probe's first and last ``KEEP`` device
operations per chip, its spans and the ``op_scopes`` of those operations
(how the tests' recorded scoped probe was made), and prints the
reduction of what it kept. Without a TPU it exits with 3.
"""
from __future__ import annotations

import argparse
import gzip
import json
import os
import re
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
LAYERS = ("arrivals", "ring_deliver", "ring_commit", "mandator", "sporades",
          "paxos", "extract")
UNSCOPED = "unscoped"
PARTS = LAYERS + (UNSCOPED,)
HOST_SPANS = ("lower", "enqueue", "readback", "decode")
SPAN_PREFIXES = ("bench.", "experiment.")
SAMPLE_S = 0.1
KEEP = 1500

_INSTR = re.compile(
    r'^\s*(?:ROOT )?%?([^\s=]+) = .*metadata=\{op_name="([^"]*)"')
_WRAPPED = re.compile(r"^\w+\((.*)\)$")
_CONTAINERS = ("while", "closed_call", "call", "conditional")


def hlo_op_scopes(hlo_text: str) -> Dict[str, str]:
    """{instruction name: op_name} of every instruction of a compiled
    module's HLO text that carries ``metadata={op_name=...}``. Names are
    unique in a module, and a trace event's name starts with one."""
    out: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def layer_of(op_name: str) -> Tuple[str, int]:
    """The deepest layer scope in an ``op_name`` path, and how many layer
    scopes enclose it: ``.../mandator/arrivals/...`` is ``("arrivals",
    2)``, a path with none ``(UNSCOPED, 0)``. A scope entered under a
    transform reads ``vmap(extract)``, which counts as ``extract``."""
    found = []
    for comp in op_name.split("/"):
        m = _WRAPPED.match(comp)
        while m:
            comp = m.group(1)
            m = _WRAPPED.match(comp)
        if comp in LAYERS:
            found.append(comp)
    return (found[-1], len(found)) if found else (UNSCOPED, 0)


def _instr(event_name: str) -> str:
    return event_name.split(" = ")[0].lstrip("%")


def scope_partition(data: dict, lo: float, hi: float
                    ) -> Dict[str, Dict[str, float]]:
    """{device: {part: ns}}: each device's busy time in [lo, hi] split
    into ``PARTS``. An operation's layer is that of its instruction's
    ``op_name`` in ``data["op_scopes"]`` (a container op such as a
    ``while`` counts for the scope in its own path; a name not there is
    unscoped); each instant goes to the deepest layer among the operations
    running then, ties to the earlier in ``PARTS``. One sort of the
    events' ends, then linear."""
    scopes = data.get("op_scopes") or {}
    rank_of: Dict[str, int] = {}
    n_parts = len(PARTS)
    out: Dict[str, Dict[str, float]] = {}
    for dev, events in sorted(data["devices"].items()):
        starts, ends, ranks = [], [], []
        for name, s, e in events:
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
            key = _instr(name)
            r = rank_of.get(key)
            if r is None:
                layer, depth = layer_of(scopes.get(key, ""))
                r = rank_of[key] = (depth * n_parts + n_parts - 1
                                    - PARTS.index(layer))
            starts.append(s)
            ends.append(e)
            ranks.append(r)
        part = out[dev] = dict.fromkeys(PARTS, 0.0)
        if not starts:
            continue
        k = len(starts)
        edges, idx = np.unique(np.asarray(starts + ends),
                               return_inverse=True)
        si, ei = idx[:k], idx[k:]
        ranks = np.asarray(ranks)
        owner = np.full(len(edges) - 1, -1)
        for r in np.unique(ranks):          # ascending: the deepest wins
            sel = ranks == r
            running = np.cumsum(np.bincount(si[sel], minlength=len(edges))
                                - np.bincount(ei[sel], minlength=len(edges)))
            owner[running[:-1] > 0] = r
        seg = np.diff(edges)
        for r in np.unique(owner[owner >= 0]):
            part[PARTS[n_parts - 1 - r % n_parts]] += float(
                seg[owner == r].sum())
    return out


def span_at(spans: List, t: float) -> str:
    """The innermost host span that holds ``t``: the one that started
    last. A ``bench.`` span reads by its short name, as ``tracing``
    names it; a program span by its full name."""
    at, at_start = "host", None
    for name, s, e in spans:
        if s <= t < e and (at_start is None or s >= at_start):
            at, at_start = name, s
    return at[len("bench."):] if at.startswith("bench.") else at


def idle_gaps(data: dict, lo: float, hi: float, top: int = 10
              ) -> List[Tuple[str, float]]:
    """The longest idle gaps of every device in [lo, hi], in seconds, each
    named by ``span_at`` its midpoint."""
    from chip_bench import tracing
    gaps = []
    for events in data["devices"].values():
        busy = tracing.union(events, lo, hi)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((span_at(data["spans"], (a + b) / 2),
                             (b - a) * 1e-9))
    return sorted(gaps, key=lambda g: -g[1])[:top]


def extract(xplane_path: str) -> dict:
    """Like ``tracing.extract`` of the ``XLA Ops`` line, with the
    program's ``experiment.*`` spans beside the benchmark's ``bench.*``."""
    from jax.profiler import ProfileData

    from chip_bench import tracing
    pd = ProfileData.from_file(xplane_path)
    devices: Dict[str, list] = {}
    spans: list = []
    for plane in pd.planes:
        m = tracing._DEVICE.match(plane.name)
        if m:
            devices[m.group(1)] = [
                (e.name, float(e.start_ns), float(e.end_ns))
                for ln in plane.lines if ln.name == tracing.OPS_LINE
                for e in ln.events]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans += [(e.name, float(e.start_ns), float(e.end_ns))
                          for e in ln.events
                          if e.name.startswith(SPAN_PREFIXES)]
    return {"devices": devices, "spans": sorted(spans, key=lambda s: s[1])}


def program_text(cell, cfg, spec) -> str:
    """The optimized HLO text of the canonical program that runs one point
    of ``spec``: looked up as a dispatch would, compiled again (from the
    persistent cache), so only after the window's counters are read."""
    import jax

    from repro.core import experiment
    _, rcfg, mode, env_b, wl_b, rate_b, seed_b, _ = experiment._lower(
        cfg, spec)
    args = jax.tree.map(lambda x: x[:1], (env_b, wl_b, rate_b, seed_b))
    fn = experiment._acquire_program(cell.protocol, rcfg, mode, args)
    return fn.lower(*args).compile().as_text()


def reduce_probe(data: dict, points: int, is_kernel) -> dict:
    """The probe's numbers: the ``tracing.summarize`` window, busy and
    kernel time, the scope partition and the named gaps, in ms a point."""
    from chip_bench import tracing
    lo, hi = (tracing.spans(data, "dispatch")[0][1],
              max(e for _, _, e in tracing.spans(data, "collect")
                  + tracing.spans(data, "sample")))
    s = tracing.summarize(data, is_kernel)
    part = scope_partition(data, lo, hi)
    busy = sum(d["busy_ns"] for d in s["devices"].values())
    ends = [d["last_op_end"] for d in s["devices"].values()
            if d["last_op_end"] is not None]
    rb = [e for n, st, e in data["spans"]
          if n == "experiment.readback" and lo <= st < hi]
    per = 1e-6 / points
    out = {"window_ns": s["window_ns"], "busy_ns": busy,
           "kernel_ns": sum(d["kernel_ns"] for d in s["devices"].values()),
           "parts_ns": {p: sum(d[p] for d in part.values()) for p in PARTS},
           "device_ms_per_point": busy * per,
           "parts_ms_per_point": {p: sum(d[p] for d in part.values()) * per
                                  for p in PARTS},
           "gaps": idle_gaps(data, lo, hi),
           "top_unscoped": top_unscoped(data, lo, hi)}
    if ends and s["collect_end"] is not None:
        out["readback_ms_per_point"] = (s["collect_end"] - max(ends)) * per
    if ends and rb:
        out["transfer_ms_per_point"] = (max(rb) - max(ends)) * per
    return out


def top_unscoped(data: dict, lo: float, hi: float, top: int = 8
                 ) -> List[Tuple[str, float]]:
    """The unscoped operations with the most summed time in [lo, hi], in
    seconds, leaving out the containers (loops and calls) that hold the
    scoped work."""
    scopes = data.get("op_scopes") or {}
    counted: Dict[str, bool] = {}
    total: Dict[str, float] = {}
    for events in data["devices"].values():
        for name, s, e in events:
            if e <= lo or s >= hi:
                continue
            key = _instr(name)
            c = counted.get(key)
            if c is None:
                c = counted[key] = (not key.startswith(_CONTAINERS) and
                                    layer_of(scopes.get(key, ""))[0]
                                    == UNSCOPED)
            if c:
                total[key] = total.get(key, 0.0) + (min(e, hi)
                                                    - max(s, lo))
    return [(k, v * 1e-9) for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:top]]


def _span_cost(experiment, n: int = 2000) -> float:
    """Microseconds a span costs, enter to exit, as the program uses it."""
    t0 = time.perf_counter()
    for _ in range(n):
        with experiment._span("span-cost", "lower"):
            pass
    return (time.perf_counter() - t0) / n * 1e6


def probe(workload: str, seed: int, grids: int = 3, keep: str | None = None,
          root: Path = ROOT, require_chip: bool = True) -> dict:
    """Run the cell's set-up, ``grids`` grids and one traced probe, and
    return the host spans a point, the probe's reduction and its cost."""
    import jax

    from chip_bench import cell as cellmod
    from chip_bench import run, tracing
    from repro.core import compile_cache, experiment

    cell = cellmod.load(workload, root)
    run.check_devices(cell.chips, require_chip)
    cfg = cellmod.smr_config(cell)
    n_seeds = cell.traffic["seeds_per_grid"]

    def dispatch(seeds, rates=None):
        spec = cellmod.sweep_spec(cell, seeds, rates)
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            return experiment.dispatch_sweep(cell.protocol, cfg, spec,
                                             mesh=cell.mesh)

    def collect(pend):
        with jax.profiler.TraceAnnotation("bench.collect"):
            return pend.collect()

    collect(dispatch(cellmod.grid_seeds(seed, -1, n_seeds)))
    # ---- host spans over grids the profiler does not see
    cache0 = compile_cache.stats()
    traces0 = dict(experiment.trace_counts())
    t0 = experiment.timing_stats()[cell.protocol]
    points, disp_s, tw = 0, 0.0, time.perf_counter()
    for i in range(grids):
        ta = time.perf_counter()
        pend = dispatch(cellmod.grid_seeds(seed, i, n_seeds))
        disp_s += time.perf_counter() - ta
        points += len(collect(pend))
    window_s = time.perf_counter() - tw
    t1 = experiment.timing_stats()[cell.protocol]
    host = {f"{k}_ms_per_point": (t1[f"{k}_s"] - t0[f"{k}_s"]) * 1e3 / points
            for k in HOST_SPANS}
    host["spans_per_grid"] = {k: (t1[f"{k}_n"] - t0[f"{k}_n"]) / grids
                              for k in HOST_SPANS}
    host["dispatch_ms_per_point"] = disp_s * 1e3 / points
    host["window_ms_per_point"] = window_s * 1e3 / points
    cost_off = _span_cost(experiment)
    # ---- the traced probe
    tmp = tempfile.mkdtemp(prefix="chip_bench_scopes_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    seeds = cellmod.grid_seeds(seed, -2, n_seeds)
    full = cell.mesh is None
    rates = cell.traffic["rates"][:1] if full else None
    jax.profiler.start_trace(tmp, profiler_options=opts)
    cost_on = _span_cost(experiment)
    if full:
        rows = collect(dispatch(seeds[:1], rates=rates))
        jax.profiler.stop_trace()
    else:
        pend = dispatch(seeds)
        with jax.profiler.TraceAnnotation("bench.sample"):
            time.sleep(SAMPLE_S)
        jax.profiler.stop_trace()
        rows = collect(pend)
    tr = time.perf_counter()
    data = extract(tracing.find_xplane(tmp))
    shutil.rmtree(tmp, ignore_errors=True)
    extract_s = time.perf_counter() - tr
    cache = compile_cache.delta(cache0)
    traces = {k: v - traces0.get(k, 0)
              for k, v in experiment.trace_counts().items()}
    compiles = (cache["persistent_cache_misses"]
                + cache["persistent_cache_hits"] + sum(traces.values()))
    # ---- the program's op_name metadata, after the counters
    if full:
        data["op_scopes"] = hlo_op_scopes(program_text(
            cell, cfg, cellmod.sweep_spec(cell, seeds[:1], rates)))
    tr = time.perf_counter()
    red = reduce_probe(data, len(rows), run.is_kernel)
    reduce_s = time.perf_counter() - tr
    out = {"workload": cell.name, "device": jax.devices()[0].device_kind,
           "chips": cell.chips, "probe_points": len(rows), "whole": full,
           "compiles": compiles, "host": host,
           "span_cost_us": {"off": cost_off, "on": cost_on},
           "extract_s": extract_s, "reduce_s": reduce_s,
           "op_scopes": len(data.get("op_scopes", {})),
           "layers_in_program": sorted({layer_of(v)[0] for v in data.get(
               "op_scopes", {}).values()}),
           "probe": red}
    if keep:
        os.makedirs(keep, exist_ok=True)
        lo = tracing.spans(data, "dispatch")[0][1]
        kept = {"devices": {k: v[:KEEP] + v[-KEEP:]
                            for k, v in data["devices"].items()},
                "spans": [sp for sp in data["spans"] if sp[2] > lo]}
        names = {_instr(n) for v in kept["devices"].values()
                 for n, _, _ in v}
        kept["op_scopes"] = {k: v for k, v in data.get("op_scopes",
                                                       {}).items()
                             if k in names}
        with gzip.open(os.path.join(keep, "probe.json.gz"), "wt") as fh:
            json.dump(kept, fh)
        out["kept"] = reduce_probe(kept, len(rows), run.is_kernel)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--grids", type=int, default=3)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(ROOT / ".jax_cache"))
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chip_bench import run
    try:
        out = probe(args.workload, args.seed, args.grids, args.keep)
    except run.NoChip as e:
        print(f"chip_bench.scopes: {e}", file=sys.stderr)
        return 3
    red = out["probe"]
    print(f"compiles in the grids and probe: {out['compiles']}; partition "
          "(ms a point): " + ", ".join(
              f"{p} {v!r}" for p, v in red["parts_ms_per_point"].items())
          + f"; device {red['device_ms_per_point']!r}; reduction "
          f"{out['reduce_s']:.3f} s", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
