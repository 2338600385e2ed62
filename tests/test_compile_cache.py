"""Compile accounting + persistent compilation cache (the "kill the
compile wall" layer): the fig 6/7/9 suites must lower to ONE canonical
program signature per protocol, a warm-cache second process must report
zero new XLA compiles with bitwise-identical results, and the
compile_cache enable/disable/ensure state machine must hold so the
pytest opt-out marker and the REPRO_COMPILE_CACHE=0 escape hatch work."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs.smr import SMRConfig
from repro.core import compile_cache, experiment
from repro.core.experiment import (
    CANONICAL_LANES,
    CANONICAL_MIN_WINDOWS,
    ProgramSignature,
    SweepSpec,
    _canon_pow2,
    _lower,
    run_sweep,
)
from repro.core.harness import run_sim
from repro.scenarios import Crash, Scenario

SRC = Path(__file__).resolve().parents[1] / "src"


# ------------------------------------------------ canonical signatures ----

def test_canon_pow2():
    assert _canon_pow2(1, 4) == 4
    assert _canon_pow2(4, 4) == 4
    assert _canon_pow2(5, 4) == 8
    assert _canon_pow2(6, 1) == 8
    assert _canon_pow2(8, 1) == 8
    assert _canon_pow2(9, 1) == 16


def _fig_specs(sim_s: float):
    """The fig 6 / 7 / 9(n=5) sweep shapes, as the benchmark builds them:
    a 4-rate grid, a 1-rate leader-crash grid, a 1-rate scalability
    point. Same cfg statics, wildly different native shapes."""
    return (
        SweepSpec(rates=(50_000, 150_000, 300_000, 450_000)),       # fig6
        SweepSpec(rates=(100_000,), scenarios=(Scenario("leader-crash", (
            Crash(start_s=sim_s / 2, targets=(0,)),)),)),            # fig7
        SweepSpec(rates=(60_000 * 5,)),                              # fig9
    )


def test_fig_suite_specs_lower_to_one_signature():
    """fig6 (4 rates, no scenario), fig7 (1 rate, crash), and fig9's n=5
    point (1 rate) produce the SAME canonical ProgramSignature — the
    lowering is protocol-independent, so this pins program sharing for
    every protocol at once without compiling anything."""
    cfg = SMRConfig(sim_seconds=2.0)  # the --quick suite length
    sigs = {_lower(cfg, spec, canonical=True)[-1]
            for spec in _fig_specs(cfg.sim_seconds)}
    assert len(sigs) == 1, f"fig 6/7/9 signatures diverged: {sigs}"
    (sig,) = sigs
    assert sig == ProgramSignature(
        n=5, ticks=2000, lanes=CANONICAL_LANES,
        scen_windows=CANONICAL_MIN_WINDOWS,
        wl_windows=CANONICAL_MIN_WINDOWS,
        horizon=256, trivial=True, closed=False)


def test_fig_shaped_sweeps_reuse_one_compiled_program():
    """End to end for mandator-sporades: running the three fig-suite
    shapes back to back traces exactly once — suites 2 and 3 reuse the
    compiled program (the same shapes at sim_seconds=1.0 to keep the
    tier-1 compile budget small; shape sharing is what is under test)."""
    cfg = SMRConfig(sim_seconds=1.0)
    experiment.reset_trace_counts()
    for spec in _fig_specs(cfg.sim_seconds):
        run_sweep("mandator-sporades", cfg, spec)
    # zero traces means an earlier test already compiled the shared
    # canonical program — the one-program claim is the signature count
    traced = experiment.trace_counts().get("mandator-sporades", 0)
    assert traced <= 1, "fig-shaped sweeps must share ONE compiled program"
    assert len(experiment.program_signatures()["mandator-sporades"]) == 1
    # and a single-point run_sim rides the same program too
    run_sim("mandator-sporades", cfg, 75_000)
    assert experiment.trace_counts().get("mandator-sporades", 0) == traced
    assert len(experiment.program_signatures()["mandator-sporades"]) == 1


def test_matrix_suite_signature_matches_fig8():
    """Satellite (warm-cache the robustness suite): the FULL scenario
    library — whose busiest schedule (gray-wan) needs up to 30 window
    rows at the 4s suite length — must lower to the SAME canonical
    signature as the fig8 paper-ddos sweep at both --quick (2s) and full
    (4s) lengths, so the robustness matrix reuses fig8's compiled program
    instead of missing the cache on a window-axis variant (the 32-row
    canonical floor is what absorbs the difference)."""
    from repro.scenarios import library as scenario_library
    for sim_s in (2.0, 4.0):
        cfg = SMRConfig(sim_seconds=sim_s)
        lib = scenario_library.scenarios(sim_s, cfg.n_replicas)
        fig8 = _lower(cfg, SweepSpec(rates=(300_000,),
                                     scenarios=(lib["paper-ddos"],)))[-1]
        robust = _lower(cfg, SweepSpec(rates=(50_000, 200_000),
                                       scenarios=tuple(lib.values())))[-1]
        assert fig8 == robust, (sim_s, fig8, robust)


def test_crowded_window_table_shares_canonical_program():
    """End to end: a 4-interval crash schedule lowers to >8 native window
    rows; the canonical floor must absorb it so the sweep reuses the
    baseline-shaped program with ZERO new traces (this is the in-process
    version of the robustness warm-cache satellite)."""
    cfg = SMRConfig(sim_seconds=0.5)
    experiment.reset_trace_counts()
    run_sweep("mandator", cfg, SweepSpec(rates=(20_000,)))
    base = experiment.trace_counts().get("mandator", 0)
    busy = Scenario("many-crashes", tuple(
        Crash(start_s=0.05 * i, end_s=0.05 * i + 0.02, targets=(i % 5,))
        for i in range(1, 5)))
    from repro import scenarios as sc
    tab = sc.lower(cfg, busy)
    assert tab["alive"].shape[0] > 8, "scenario must exceed the old floor"
    run_sweep("mandator", cfg, SweepSpec(rates=(20_000,), scenarios=(busy,)))
    assert experiment.trace_counts().get("mandator", 0) == base, \
        "crowded window table must reuse the canonical program"
    assert len(experiment.program_signatures()["mandator"]) == 1


def test_native_lowering_keeps_exact_shapes():
    cfg = SMRConfig(sim_seconds=1.0)
    spec = SweepSpec(rates=(10_000, 20_000))
    sig = _lower(cfg, spec, canonical=False)[-1]
    assert (sig.lanes, sig.scen_windows, sig.wl_windows) == (2, 1, 1)


def test_compile_report_shape():
    experiment.reset_trace_counts()
    rep = experiment.compile_report()
    assert set(rep) == {"traces", "programs", "signatures", "cache"}
    for k in compile_cache.STAT_KEYS:
        assert k in rep["cache"]


# ------------------------------------------- persistent cache plumbing ----

@pytest.mark.no_persistent_cache
def test_enable_disable_and_counters(tmp_path, monkeypatch):
    """A fresh jit compiles into the dir JAX_COMPILATION_CACHE_DIR names
    (miss); re-compiling the same program after clearing the in-memory
    jit caches loads it back (hit) instead of recompiling."""
    import jax
    import jax.numpy as jnp

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    compile_cache.enable()
    try:
        assert compile_cache.enabled()
        assert compile_cache.cache_dir() == tmp_path

        def fresh(x):
            return jnp.sin(x) * 3.0 + jnp.cos(x)

        before = compile_cache.stats()
        jax.jit(fresh)(jnp.arange(7.0)).block_until_ready()
        d = compile_cache.delta(before)
        assert d["persistent_cache_misses"] >= 1
        assert any(tmp_path.iterdir()), "no executable written to cache dir"

        jax.clear_caches()
        before = compile_cache.stats()
        jax.jit(fresh)(jnp.arange(7.0)).block_until_ready()
        d = compile_cache.delta(before)
        assert d["persistent_cache_hits"] >= 1
        assert d["persistent_cache_misses"] == 0
    finally:
        compile_cache.disable()


@pytest.mark.no_persistent_cache
def test_ensure_respects_explicit_disable(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    compile_cache.disable()
    assert compile_cache.ensure() is None, \
        "ensure() must not undo an explicit disable()"
    compile_cache.enable()
    assert compile_cache.ensure() == tmp_path
    compile_cache.disable()
    assert not compile_cache.enabled()
    assert compile_cache.cache_dir() is None


def test_ensure_respects_env_opt_out(monkeypatch):
    monkeypatch.setenv(compile_cache.DISABLE_ENV, "0")
    was = compile_cache.enabled()
    # must not flip the cache on when the env says no (and must not
    # disable an already-enabled cache either)
    assert (compile_cache.ensure() is not None) == was


# --------------------------------------- warm process compiles nothing ----

_SWEEP_SCRIPT = """\
import json
from repro.core import compile_cache, experiment
from repro.configs.smr import SMRConfig
from repro.core.experiment import SweepSpec, run_sweep

compile_cache.enable()
cfg = SMRConfig(sim_seconds=0.4)
res = run_sweep("mandator", cfg, SweepSpec(rates=(20_000, 60_000)))
rep = experiment.compile_report()
out = {
    "misses": rep["cache"]["persistent_cache_misses"],
    "hits": rep["cache"]["persistent_cache_hits"],
    "backend_compile_s": rep["cache"]["backend_compile_s"],
    "traces": rep["traces"],
    "results": [{
        "throughput": repr(r["throughput"]),
        "median_ms": repr(r["median_ms"]),
        "p99_ms": repr(r["p99_ms"]),
        "committed": repr(r["committed"]),
        "timeline": [repr(float(x)) for x in r["timeline"]],
    } for r in res],
}
print(json.dumps(out, sort_keys=True))
"""


def _run_sweep_subprocess(cache_dir: Path) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = str(SRC) + os.pathsep + env.get("PYTHONPATH", "")
    # scope the subprocess strictly to the pinned dir
    env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    out = subprocess.run(
        [sys.executable, "-c", _SWEEP_SCRIPT],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, f"subprocess failed:\n{out.stderr}"
    return json.loads(out.stdout)


def test_warm_cache_process_zero_new_compiles_bitwise_identical(tmp_path):
    """The tentpole claim, end to end: with the cache dir pinned, a second
    process running the same sweep reports ZERO persistent-cache misses
    (every XLA executable is loaded, none compiled) and produces
    bitwise-identical metrics (compared via repr round-trip, which is
    exact for floats)."""
    cold = _run_sweep_subprocess(tmp_path)
    assert cold["misses"] > 0, "cold run must populate the cache"
    assert cold["traces"] == {"mandator": 1}

    warm = _run_sweep_subprocess(tmp_path)
    assert warm["misses"] == 0, \
        f"warm run recompiled {warm['misses']} programs"
    assert warm["hits"] >= cold["misses"]
    assert warm["traces"] == {"mandator": 1}, \
        "tracing still happens per process (only XLA compile is cached)"
    assert warm["results"] == cold["results"], \
        "warm-cache results must be bitwise identical"


def test_saved_time_counter_clamps_negative_events():
    """jax reports compile_time_saved per hit as (estimated compile) -
    (retrieval cost), which goes negative for cheap programs — raw
    accumulation made whole suites report negative savings. The listener
    clamps per event: negatives are dropped, positives accumulate."""
    before = compile_cache.stats()["compile_saved_s"]
    compile_cache._on_duration(compile_cache._DUR_SAVED,
                               duration_secs=-0.5)
    assert compile_cache.stats()["compile_saved_s"] == pytest.approx(before)
    compile_cache._on_duration(compile_cache._DUR_SAVED, duration_secs=0.25)
    compile_cache._on_duration(compile_cache._DUR_SAVED,
                               duration_secs=-1.25)
    assert compile_cache.stats()["compile_saved_s"] == pytest.approx(
        before + 0.25)
