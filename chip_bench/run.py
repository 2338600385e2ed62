"""Simulated grid points per second on the chip, for one benchmark cell.

    python3 chip_bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

One process, one cell, one run. Set-up loads the cell's program (from the
persistent compilation cache and the program store once a first run has
filled them) and runs one warm-up grid of the cell's shape. The window is
one client in a closed loop: it submits the cell's grid through
``experiment.dispatch_sweep``, waits for ``collect()``, and submits the
next grid, each with fresh seeds drawn from ``--seed`` and the grid's
index, until ``--seconds`` have passed; the grid in flight finishes.

Then a sample of the finished points, drawn from the seed, is run again
by the plain reference (``reference.py``) and compared (``check.py``);
``correct`` is whether every compared number is inside its limit.

``--trace 0`` reports the end-to-end metrics. ``--trace 1`` runs the same
window, then traces one probe of the cell's program (``_probe``) and
reports the per-layer metrics, each read by ``metrics/<name>.py`` from
the probe's trace and the window's host timings.

The last line of standard output is one JSON object; the compared numbers
with their limits are the last lines of standard error. Without a TPU, or
with another number of chips than the cell asks for, it exits with 3 and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
SAMPLE_S = 0.1          # a sharded grid's probe: its first stretch


def is_kernel(op_name: str) -> bool:
    """The ring-commit kernel's operations: the program's only Pallas
    kernel is the ring commit, and on the TPU a Pallas kernel is a custom
    call to the ``tpu_custom_call`` target."""
    return 'custom_call_target="tpu_custom_call"' in op_name


class NoChip(Exception):
    """JAX finds no TPU, or another number of chips than the cell asks."""


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _keep(row: dict) -> dict:
    """The fields of a result row that the check compares."""
    from chip_bench import check
    keys = check.FLOAT_KEYS + check.STATE_KEYS + ("sketch",)
    return {k: row[k] for k in keys if k in row}


def check_devices(chips: int, require_chip: bool):
    import jax
    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's default backend is {devs[0].platform!r}")
    if require_chip and len(devs) != chips:
        raise NoChip(f"the cell asks for {chips} chip(s), JAX reports "
                     f"{len(devs)}")
    return devs


def _metric_readers(bench: dict, cell_name: str, root: Path):
    out = []
    for m in bench["per_layer"]:
        if "workloads" in m and cell_name not in m["workloads"]:
            continue
        path = root / "chip_bench" / "metrics" / f"{m['name']}.py"
        spec = importlib.util.spec_from_file_location(
            f"chip_bench_metric_{m['name'].replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        out.append((m, mod))
    return out


def _probe(cell, cfg, seed: int, dispatch, collect, host, horizon: int,
           root: Path, keep_trace):
    """Trace one probe of the cell's program and build the context the
    metric readers read. Without a mesh the probe is one rate and seed of
    the grid (same program), traced whole; with a mesh it is a whole
    sharded grid, traced over its first ``SAMPLE_S`` seconds."""
    import jax

    from chip_bench import cell as cellmod
    from chip_bench import reference, ringbytes
    from chip_bench import tracing as tr
    tmp = tempfile.mkdtemp(prefix="chip_bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    full = cell.mesh is None
    seeds = cellmod.grid_seeds(seed, -2, cell.traffic["seeds_per_grid"])
    jax.profiler.start_trace(tmp, profiler_options=opts)
    if full:
        pend = dispatch(seeds[:1], rates=cell.traffic["rates"][:1])
        rows = collect(pend)
        jax.profiler.stop_trace()
    else:
        pend = dispatch(seeds)
        with jax.profiler.TraceAnnotation("bench.sample"):
            time.sleep(SAMPLE_S)
        jax.profiler.stop_trace()
        rows = collect(pend)
    data = tr.extract(tr.find_xplane(tmp), tr.OPS_LINE)
    shutil.rmtree(tmp, ignore_errors=True)
    if keep_trace:
        os.makedirs(keep_trace, exist_ok=True)
        kern = next((n for evs in data["devices"].values()
                     for n, _, _ in evs if is_kernel(n)), None)
        _log(f"a kernel operation's full name: {kern}")
        with open(os.path.join(keep_trace, "probe.json"), "w") as fh:
            json.dump({"devices": {k: v[:3000] + v[-3000:]
                                   for k, v in data["devices"].items()},
                       "spans": data["spans"]}, fh)
    probe = tr.summarize(data, is_kernel)
    with open(root / "chip_bench" / "peaks.json") as fh:
        peaks = json.load(fh)
    kind = jax.devices()[0].device_kind
    if kind not in peaks:
        raise RuntimeError(f"no peaks for device kind {kind!r} in "
                           f"peaks.json")
    n = cfg.n_replicas
    ctx = {"probe": probe, "full": full, "points": len(rows),
           "host": host, "peaks": peaks[kind],
           "ticks_per_point": reference.n_ticks(cell.ref_cfg()),
           "ring_bytes_per_tick": ringbytes.tick_bytes(cell.protocol, n)}
    _log(f"probe: {len(rows)} points, {'whole' if full else 'sampled'}, "
         f"window {probe['window_ns'] * 1e-9:.6f} s; "
         + ", ".join(f"device {k}: {v['n_ops']} ops, busy "
                     f"{v['busy_ns'] * 1e-9:.6f} s, kernel "
                     f"{v['kernel_ns'] * 1e-9:.6f} s"
                     for k, v in probe["devices"].items()))
    _log(f"ring commit: {ctx['ring_bytes_per_tick']} bytes per tick of "
         f"commit work; the dense pass moves "
         f"{ringbytes.dense_pass_bytes(cell.protocol, n, horizon)} bytes "
         f"per tick at D = {horizon}")
    return ctx


def run(workload: str, seed: int, seconds: float, trace: bool,
        root: Path = ROOT, require_chip: bool = True,
        keep_trace: str | None = None) -> dict:
    from chip_bench import cell as cellmod
    from chip_bench import check, reference, ref_tables

    cell = cellmod.load(workload, root)
    phases = [("start", time.perf_counter())]
    import jax
    phases.append(("import jax", time.perf_counter()))
    devs = check_devices(cell.chips, require_chip)
    phases.append(("devices", time.perf_counter()))
    from repro.core import compile_cache, experiment
    from repro.kernels.channel_ring.ops import resolve_backend
    phases.append(("import program", time.perf_counter()))

    cfg = cellmod.smr_config(cell)
    backend = resolve_backend(cfg.channel_backend)
    _log(f"cell {cell.name}: {cell.protocol}, {cell.points_per_grid} "
         f"points per grid, mesh {cell.mesh}, ring backend {backend}, "
         f"devices {[d.device_kind for d in devs]}")
    per_grid = cell.points_per_grid
    n_seeds = cell.traffic["seeds_per_grid"]

    def dispatch(seeds, rates=None):
        spec = cellmod.sweep_spec(cell, seeds, rates)
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            return experiment.dispatch_sweep(cell.protocol, cfg, spec,
                                             mesh=cell.mesh)

    def collect(pend):
        with jax.profiler.TraceAnnotation("bench.collect"):
            return pend.collect()

    # ---- set-up: one warm-up grid of the cell's shape
    pend = dispatch(cellmod.grid_seeds(seed, -1, n_seeds))
    phases.append(("warm-up dispatch", time.perf_counter()))
    warm = collect(pend)
    phases.append(("warm-up collect", time.perf_counter()))
    if len(warm) != per_grid:
        raise RuntimeError(f"warm-up grid returned {len(warm)} of "
                           f"{per_grid} points")
    horizon = experiment.timing_stats()[cell.protocol]["horizon"]
    setup_s = time.perf_counter() - T_START
    del warm
    _log(f"set-up {setup_s:.3f} s (ring {horizon} slots): "
         f"{phases[0][1] - T_START:.3f} s to the cell, "
         + ", ".join(f"{name} {t - prev:.3f} s" for (_, prev), (name, t)
                     in zip(phases, phases[1:])))

    # ---- the window
    cache0 = compile_cache.stats()
    traces0 = dict(experiment.trace_counts())
    kept, host, attempted, failed = [], [], 0, 0
    t0 = time.perf_counter()
    i = 0
    while time.perf_counter() - t0 < seconds:
        attempted += per_grid
        seeds = cellmod.grid_seeds(seed, i, n_seeds)
        ta = time.perf_counter()
        pend = dispatch(seeds)
        tb = time.perf_counter()
        rows = collect(pend)
        host.append((tb - ta, time.perf_counter() - tb, len(rows)))
        failed += per_grid - len(rows) + sum(
            1 for r in rows if not r["throughput"] == r["throughput"])
        for j in cellmod.pick(seed, f"grid{i}",
                              cell.traffic["check_per_grid"], len(rows)):
            kept.append((j, seeds, _keep(rows[j])))
        del rows, pend
        i += 1
    t1 = time.perf_counter()
    points = sum(h[2] for h in host)
    used = devs[:cell.chips] if require_chip else devs[:1]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    metrics = {}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    breakdown = None
    if trace:
        ctx = _probe(cell, cfg, seed, dispatch, collect, host, horizon,
                     root, keep_trace)
        busy = [d["busy_ns"] for d in ctx["probe"]["devices"].values()]
        device["busy_s"] = sum(busy) / max(len(busy), 1) * 1e-9
        device["window_s"] = ctx["probe"]["window_ns"] * 1e-9
        bench = json.loads((root / "BENCHMARK.json").read_text())
        for m, mod in _metric_readers(bench, cell.name, root):
            v = mod.read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        breakdown = {"device_ops": ctx["probe"]["top_ops"],
                     "idle_gaps": [[k, v] for k, v in ctx["probe"]["gaps"]]}
    else:
        metrics["points_per_s"] = {"value": points / (t1 - t0),
                                   "unit": "points/s"}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    cache = compile_cache.delta(cache0)
    traces = {k: v - traces0.get(k, 0)
              for k, v in experiment.trace_counts().items()}
    compiles = (cache["persistent_cache_misses"]
                + cache["persistent_cache_hits"] + sum(traces.values()))
    _log(f"window: {i} grids, {points} points in {t1 - t0:.3f} s; "
         f"compiles in the window{' and probe' if trace else ''} "
         f"{compiles} (cache misses {cache['persistent_cache_misses']}, "
         f"hits {cache['persistent_cache_hits']}, traces {traces})")
    gc.collect()

    # ---- the check: a sample of the finished points, by the reference
    t_ref = time.perf_counter()
    sample = [kept[k] for k in cellmod.pick(
        seed, "check", cell.traffic["check_points"], len(kept))]
    pts = [cellmod.points(cell, seeds)[j] for j, seeds, _ in sample]
    rc = cell.ref_cfg()
    tabs = [ref_tables.scenario_tables(s["primitives"], rc["n_replicas"],
                                       rc["tick_ms"], reference.n_ticks(rc))
            for s in cell.traffic["scenarios"]]
    d = reference.horizon(rc, tabs)
    ref = reference.simulate(rc, pts, d, reduced=cell.mesh is not None)
    numbers = check.compare([row for *_, row in sample], ref)
    lim = check.limits()
    if d != horizon:
        numbers["horizon_gap"] = {"value": abs(d - horizon), "at": "slots"}
        lim["horizon_gap"] = 0.0
    _log(f"check: {len(sample)} points by the reference in "
         f"{time.perf_counter() - t_ref:.3f} s, ring {d} slots")
    correct = (failed == 0 and len(sample) > 0
               and all(v["value"] <= lim[k] for k, v in numbers.items()))
    checked = {k: {"value": v["value"], "limit": lim[k]}
               for k, v in numbers.items()}
    for k, v in numbers.items():
        _log(f"check {k} {v['value']!r} limit {lim[k]!r} ({v['at']})")
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = checked
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="with --trace 1, also write the probe's first and "
                         "last device operations here (how the tests' "
                         "recorded trace was made)")
    args = ap.parse_args(argv)
    # the compile cache and program store live in the checkout unless the
    # environment names a directory
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(ROOT / ".jax_cache"))
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    try:
        out = run(args.workload, args.seed, args.seconds, bool(args.trace),
                  keep_trace=args.keep_trace)
    except NoChip as e:
        _log(f"chip_bench: {e}")
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
