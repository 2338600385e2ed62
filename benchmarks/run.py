# One function per paper table/figure. Prints ``name,us_per_call,derived``.
"""Benchmark driver:
  PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig6,channel,...]
      [--no-compile-cache]

Figure suites dispatch through the batched experiment engine
(repro.core.experiment): each protocol's whole rate grid compiles once and
runs as a single vmapped program — and, since the canonical-program-
signature work, the fig 6/7/9 suites all reuse ONE compiled program per
protocol, so only the first suite pays a trace.

The persistent XLA compilation cache (repro.core.compile_cache) is enabled
by default at the repo-local ``.jax_cache`` directory
(``JAX_COMPILATION_CACHE_DIR`` overrides), so a repeat
run — another process, CI with the cache restored — skips XLA compilation
entirely and pays only tracing.

Every run also writes ``BENCH_core.json`` at the repo root: per-suite
wall-clock at millisecond precision, the compile-vs-run split, the
compile-accounting fields (jit traces, distinct program signatures,
persistent-cache hits/misses, true backend-compile seconds), and the
resolved channel-ring horizon — so the perf trajectory is tracked across
PRs. The microbench suite (channel) gets its compile/run split from
the jax.monitoring backend-compile counters instead of the sweep engine's
dispatch timers. The ``channel`` suite's packed-vs-legacy comparison lands
in ``benchmarks/artifacts/channel_bench.json``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from benchmarks import figures  # noqa: E402
from benchmarks import roofline  # noqa: E402
from benchmarks.bench_kernels import bench_channel  # noqa: E402
from repro.core import compile_cache, experiment  # noqa: E402
from repro.obs import history  # noqa: E402
from repro.obs import monitor as obs_monitor  # noqa: E402

REPO = Path(__file__).resolve().parents[1]

# per-suite extra BENCH_core blocks filled in by suite functions (the
# channel suite's HLO/roofline analysis); merged into the suite entries
_EXTRA: dict = {}


def sanitize_entry(entry: dict) -> dict:
    """Report-layer hygiene for one BENCH_core suite entry. The cache
    counters clamp per event inside compile_cache, but entries written by
    OLDER revisions (merged back in by partial ``--only`` runs) can still
    carry a negative ``cache_saved_s`` — clamp here too so the tracked
    file never shows negative savings regardless of which revision wrote
    the stale entry."""
    e = dict(entry)
    if "cache_saved_s" in e:
        try:
            e["cache_saved_s"] = round(max(float(e["cache_saved_s"]), 0.0),
                                       3)
        except (TypeError, ValueError):
            pass
    return e


def merge_suites(prev: dict, current: dict) -> dict:
    """Fold this run's suite entries over a previous BENCH_core.json
    (partial ``--only`` runs update just the suites they ran), sanitizing
    BOTH sides at the merge layer."""
    merged: dict = {}
    if isinstance(prev, dict):
        for n, e in (prev.get("suites") or {}).items():
            if isinstance(e, dict):
                merged[n] = sanitize_entry(e)
    for n, e in current.items():
        merged[n] = sanitize_entry(e)
    return merged


def _scaling_suite(quick: bool) -> list:
    """Mesh-sharded sweep engine curve (figures.scaling_curve): ~10^3
    points through ``dispatch_sweep(mesh=...)`` per available device
    count. Multi-device on CPU requires
    XLA_FLAGS=--xla_force_host_platform_device_count=8 in the job env."""
    rows = figures.scaling_curve(sim_seconds=0.25 if quick else 0.5)
    _EXTRA["scaling"] = {
        "scaling": figures.SCALING.pop("scaling"),
        # opcode-level HBM attribution of the per-point program (where the
        # packed ring scatter sits now that run time is the bottleneck)
        "sweep_hlo": roofline.sweep_hlo_block(0.25 if quick else 0.5),
    }
    return rows


def _channel_suite() -> list:
    rows = bench_channel()
    art = {r[0]: {"us_per_tick": r[1], "derived": r[2]} for r in rows}
    (figures.ART / "channel_bench.json").write_text(
        json.dumps(art, indent=1))
    # HLO cost + roofline terms of the packed loop just timed above
    _EXTRA["channel"] = {"hlo_roofline": roofline.channel_hlo_block()}
    return rows


def _hlo_audit_suite(sim_s: float) -> list:
    """Tracelint as a benchmark suite: AST repo lint + HLO program audit
    (repro.analysis). The monitor-shaped verdict lands in the suite's
    ``monitor`` key so H1–H4 violations gate through history.compare like
    runtime invariant violations; per-rule active-finding counts land in
    the ``analysis`` block so lint debt is a trajectory."""
    from repro.analysis import hlo_lint, run_lint
    report = run_lint(REPO / "src" / "repro")
    verdict = hlo_lint.audit(sim_seconds=sim_s, report=report)
    figures.VERDICTS["hlo-audit"] = verdict
    counts = {"active": len(report.active)}
    counts.update(report.counts())
    _EXTRA["hlo-audit"] = {"analysis": counts}
    rows = []
    for proto, d in verdict["protocols"].items():
        if d.get("program", "x") is None:
            rows.append((f"hlo-audit/{proto}", 0.0, "analytic:clean"))
        else:
            rows.append((f"hlo-audit/{proto}", 0.0,
                         f"f64={d['f64_ops']};xfer_in_loop="
                         f"{d['host_transfers_in_loop']};"
                         f"scan_while={d['scan_whiles']}"))
    for tag, sigs in verdict["signatures"].items():
        rows.append((f"hlo-audit/grid-{tag}", 0.0,
                     f"signatures={len(sigs)}"))
    rows.append(("hlo-audit/ast", 0.0,
                 f"active={len(report.active)};"
                 f"findings={len(report.findings)}"))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="shorter sims (2s instead of 4s)")
    ap.add_argument("--only", default="")
    ap.add_argument("--no-compile-cache", action="store_true",
                    help="disable the persistent XLA compilation cache "
                         "(every process recompiles)")
    ap.add_argument("--no-history", action="store_true",
                    help="skip the BENCH_history.jsonl append + "
                         "regression comparison")
    args, _ = ap.parse_known_args()
    sim_s = 2.0 if args.quick else 4.0
    only = set(args.only.split(",")) if args.only else None

    if args.no_compile_cache:
        compile_cache.disable()
    else:
        cache_dir = compile_cache.enable()
        print(f"# persistent compile cache: {cache_dir}", file=sys.stderr)

    figures.ART.mkdir(parents=True, exist_ok=True)
    suites = {
        "fig6": lambda: figures.fig6_throughput_latency(sim_s),
        "fig7": lambda: figures.fig7_crash(sim_s),
        "fig8": lambda: figures.fig8_ddos(sim_s),
        "fig9": lambda: figures.fig9_scalability(max(sim_s - 1, 2.0)),
        "robustness": lambda: figures.robustness(sim_s),
        "workload-matrix": lambda: figures.workload_matrix(sim_s),
        "scaling": lambda: _scaling_suite(args.quick),
        "paper": figures.paper_comparison,
        "channel": _channel_suite,
        "hlo-audit": lambda: _hlo_audit_suite(sim_s),
    }
    if only:
        unknown = only - suites.keys()
        if unknown:
            sys.exit(f"unknown suite(s): {', '.join(sorted(unknown))}; "
                     f"valid: {', '.join(suites)}")
    print("name,us_per_call,derived")
    errored = []
    bench_core: dict = {"suites": {}}
    # traces/signatures accumulate ACROSS suites (per-suite deltas below):
    # resetting between suites would hide that fig7/fig9 reuse fig6's
    # canonical program — a 0-trace suite is the headline, not an artifact
    experiment.reset_trace_counts()
    for name, fn in suites.items():
        if only and name not in only:
            continue
        experiment.reset_timing_stats()
        cache0 = compile_cache.stats()
        traces0 = sum(experiment.trace_counts().values())
        t0 = time.perf_counter()
        suite_error = None
        try:
            for row in fn():
                print(f"{row[0]},{row[1]:.1f},{row[2]}")
        except Exception as e:  # noqa: BLE001
            print(f"{name}/ERROR,0,{type(e).__name__}:{e}")
            errored.append(name)
            suite_error = type(e).__name__
        wall = time.perf_counter() - t0
        stats = experiment.timing_stats()
        cache_d = compile_cache.delta(cache0)
        # 3-decimal (ms) precision everywhere: warm-cache suites run in
        # milliseconds, and "0.0" is not a trajectory point
        entry = {
            # per-suite so merged files can't mix quick/full timings
            # under one misleading top-level flag
            "quick": args.quick,
            "wall_s": round(wall, 3),
            # first-dispatch (trace+compile+first run) vs cache-hit split
            # from the sweep engine; microbench suites have no sweep
            # dispatches, so their split comes from the monitoring-based
            # backend-compile counters instead
            "compile_s": round(sum(s["compile_s"] for s in stats.values()),
                               3),
            "run_s": round(sum(s["run_s"] for s in stats.values()), 3),
            # compile accounting (repro.core.compile_cache + experiment):
            # jit traces this suite, true XLA backend-compile seconds, and
            # persistent-cache traffic — a warm suite shows traces>0 but
            # misses==0 and xla_compile_s~0
            "traces": sum(experiment.trace_counts().values()) - traces0,
            "xla_compile_s": round(cache_d["backend_compile_s"], 3),
            "cache_hits": cache_d["persistent_cache_hits"],
            "cache_misses": cache_d["persistent_cache_misses"],
            "cache_saved_s": round(max(cache_d["compile_saved_s"], 0.0), 3),
        }
        if not stats:
            entry["compile_s"] = entry["xla_compile_s"]
            entry["run_s"] = round(wall - cache_d["backend_compile_s"], 3)
        if suite_error is not None:
            # a partial run's wall-clock is not a trajectory point —
            # mark it so cross-PR comparisons can filter it out
            entry["error"] = suite_error
        horizons = {p: s["horizon"] for p, s in stats.items()
                    if s.get("horizon")}
        if horizons:
            entry["ring_horizon"] = horizons
        entry.update(_EXTRA.pop(name, {}))
        # flight-recorder telemetry (phase breakdowns; only present when
        # REPRO_TRACE != off, so default BENCH_core entries are unchanged)
        tele = figures.TELEMETRY.pop(name, None)
        if tele:
            entry["telemetry"] = tele
        # health-monitor verdict (only present when REPRO_MONITOR != off):
        # aggregated over every sweep point the suite collected
        mverdict = figures.VERDICTS.pop(name, None)
        if mverdict is not None:
            entry["monitor"] = mverdict
        bench_core["suites"][name] = entry
        msg = (f"# {name} done in {wall:.2f}s "
               f"({entry['traces']} new traces, "
               f"{entry['cache_misses']} compile-cache misses")
        if mverdict is not None:
            msg += f", {obs_monitor.format_verdict(mverdict)}"
        print(msg + ")", file=sys.stderr)
    # distinct canonical programs per protocol, across every suite run
    bench_core["programs"] = {
        p: len(s) for p, s in experiment.program_signatures().items()}
    # history entry covers THIS run's suites only — snapshot before the
    # merge below folds in stale suites from a previous BENCH_core.json
    run_suites = {n: dict(e) for n, e in bench_core["suites"].items()}
    # merge into the tracked trajectory file: partial (--only) runs update
    # just the suites they ran instead of discarding the rest
    bench_path = REPO / "BENCH_core.json"
    if bench_path.exists():
        try:
            prev = json.loads(bench_path.read_text())
            bench_core["suites"] = merge_suites(prev, bench_core["suites"])
        except (json.JSONDecodeError, AttributeError):
            pass
    bench_path.write_text(json.dumps(bench_core, indent=1) + "\n")
    if run_suites and not args.no_history:
        # append-and-compare ledger: every run lands one schema-validated
        # line in BENCH_history.jsonl; the comparison against the previous
        # entry is what the CI health job gates on
        hist_path = REPO / "BENCH_history.jsonl"
        base = history.latest(hist_path)
        entry = history.make_entry(run_suites, quick=args.quick,
                                   git_sha=history.git_sha(REPO),
                                   timestamp=time.time())
        history.append(hist_path, entry)
        cmp_res = history.compare(base, entry)
        for line in history.format_compare(cmp_res):
            print(f"# history: {line}", file=sys.stderr)
    if errored:
        sys.exit(f"suite(s) errored: {', '.join(errored)}")


if __name__ == "__main__":
    main()
