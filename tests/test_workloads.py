"""Workload engine (repro/workloads): primitive -> rate-table lowering
invariants (conservation against each primitive's analytic expectation),
closed-loop in-flight bounds, the trivial fast path that keeps the fig 6-9
artifacts byte-identical (uniform table path == seed-era scalar path,
bitwise), heterogeneous workload grids batching through run_sweep as ONE
compiled program, and the analytic baselines consuming the same compiled
tables."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jcore

from repro.configs.smr import SMRConfig
from repro.core import experiment, harness, netsim, workload
from repro.core.experiment import SweepSpec, run_sweep
from repro.core.harness import run_sim
from repro.scenarios import library as scenario_library
from repro.workloads import (
    ClosedLoop,
    DiurnalRamp,
    FlashCrowd,
    OnOffBurst,
    PoissonOpen,
    RegionSkew,
    Workload,
    as_workload,
    is_trivial,
    lower,
    mode_of,
)
from repro.workloads import library

CFG = SMRConfig(sim_seconds=2.0)
N = CFG.n_replicas
SCALARS = ("throughput", "median_ms", "p99_ms", "committed")


def _offered(cfg, wl):
    """Mean per-origin rate multiplier over the whole run, [n]."""
    tab = lower(cfg, wl)
    return tab["rate_of"][tab["win_of_tick"]].mean(axis=0)


def _assert_point_equal(a, b):
    for k in SCALARS:
        assert (a[k] == b[k]) or (np.isnan(a[k]) and np.isnan(b[k])), \
            f"{k}: {a[k]} != {b[k]}"
    np.testing.assert_array_equal(a["timeline"], b["timeline"])


# ------------------------------------------------- lowering invariants ----

def test_onoff_burst_conserves_analytic_load():
    """Total offered load == duty*on + (1-duty)*off, exactly, when the
    period divides the run (windows align with tick edges)."""
    for duty, on, off in ((0.5, 2.0, 0.0), (0.4, 2.5, 0.0), (0.25, 2.0, 1.0)):
        wl = Workload("b", (OnOffBurst(period_s=0.5, duty=duty,
                                       on_scale=on, off_scale=off),))
        want = duty * on + (1 - duty) * off
        np.testing.assert_allclose(_offered(CFG, wl), want, rtol=1e-6)


def test_diurnal_ramp_averages_midpoint():
    wl = Workload("d", (DiurnalRamp(period_s=2.0, low=0.25, high=1.75,
                                    step_s=0.125),))
    np.testing.assert_allclose(_offered(CFG, wl), (0.25 + 1.75) / 2,
                               rtol=2e-3)


def test_flash_crowd_rectangle_analytic():
    """decay_s=0 is a clean rectangle: target origin gains exactly
    (magnitude-1) x duration/sim extra load; others are untouched."""
    wl = Workload("f", (FlashCrowd(at_s=0.5, duration_s=0.5, magnitude=8.0,
                                   targets=(2,), decay_s=0.0),))
    got = _offered(CFG, wl)
    want = np.ones(N)
    want[2] = 1.0 + (8.0 - 1.0) * 0.5 / CFG.sim_seconds
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_region_skew_conserves_and_migrates():
    wl = Workload("s", (RegionSkew(hot_frac=0.8, hot=(0,), migrate_s=0.5),))
    tab = lower(CFG, wl)
    # every window conserves the total (sum of multipliers == n)
    np.testing.assert_allclose(tab["rate_of"].sum(axis=1), N, rtol=1e-6)
    # the hotspot visits 0,1,2,3 across the four migration windows
    hot_of_win = tab["rate_of"].argmax(axis=1)
    assert hot_of_win.tolist() == [0, 1, 2, 3]
    assert tab["rate_of"][0, 0] == pytest.approx(N * 0.8)
    assert tab["rate_of"][0, 1] == pytest.approx(N * 0.2 / (N - 1))


def test_closed_loop_lowering_and_validation():
    tab = lower(CFG, Workload("c", (ClosedLoop(think_ms=40.0, cap=64.0),)))
    assert float(tab["closed"]) == 1.0
    assert float(tab["think_ticks"]) == 40.0 / CFG.tick_ms
    assert float(tab["cap"]) == 64.0
    with pytest.raises(ValueError, match="one ClosedLoop"):
        lower(CFG, Workload("cc", (ClosedLoop(), ClosedLoop())))
    with pytest.raises(ValueError, match="placement"):
        lower(CFG, Workload("cp", (ClosedLoop(placement=(1.0, 2.0)),)))
    # geo placement redistributes but conserves
    w = (0.4, 0.3, 0.15, 0.1, 0.05)
    tab = lower(CFG, Workload("cg", (ClosedLoop(placement=w),)))
    np.testing.assert_allclose(tab["rate_of"][0], np.array(w) * N, rtol=1e-6)


def test_trivial_detection_and_mode():
    assert is_trivial(lower(CFG, None))
    assert is_trivial(lower(CFG, Workload("p", (PoissonOpen(),))))
    assert not is_trivial(lower(CFG, Workload("p2", (PoissonOpen(2.0),))))
    assert not is_trivial(lower(CFG, library.get("onoff-burst", 2.0)))
    mode = mode_of([lower(CFG, None),
                    lower(CFG, library.get("closed-loop", 2.0))])
    assert (mode.trivial, mode.closed) == (False, True)
    with pytest.raises(TypeError):
        as_workload("poisson-open")


def test_library_compiles_and_pads():
    lib = library.workloads(CFG.sim_seconds, N)
    assert set(library.NAMES) == set(lib)
    from repro.workloads import compile as wcompile
    pad = max(wcompile.n_windows(CFG, w) for w in lib.values())
    for w in lib.values():
        tab = lower(CFG, w, pad_windows=pad)
        assert tab["rate_of"].shape == (pad, N)
    with pytest.raises(KeyError, match="unknown workload"):
        library.get("tsunami", 2.0)


# ------------------------------------------------- simulator semantics ----

def test_trivial_and_uniform_table_paths_agree_bitwise():
    """The pin behind the byte-identical fig 6-9 artifacts: an all-ones
    rate table forced down the non-trivial gather path produces exactly
    the seed-era scalar-broadcast results."""
    cfg = SMRConfig(sim_seconds=1.0)
    # on == off == 1.0 keeps the table all-ones but W > 1, defeating the
    # trivial fast-path detection
    uniform = Workload("uniform", (OnOffBurst(period_s=0.25, duty=0.5,
                                              on_scale=1.0, off_scale=1.0),))
    assert not is_trivial(lower(cfg, uniform))
    for proto in ("mandator-sporades", "multipaxos"):
        a = run_sim(proto, cfg, rate_tx_s=20_000)
        b = run_sim(proto, cfg, rate_tx_s=20_000, workload=uniform)
        _assert_point_equal(a, b)
        np.testing.assert_array_equal(a["origin_timeline"],
                                      b["origin_timeline"])


ONOFF = Workload("onoff", (OnOffBurst(period_s=0.25, duty=0.5,
                                       on_scale=2.5, off_scale=0.0),))
CLOSED = Workload("closed", (ClosedLoop(think_ms=50.0, cap=4000.0),))


def _draws_in_scan(seed, lam_t):
    """The per-tick draw as a scan step makes it: one sampler call per
    tick on ``fold_in(PRNGKey(seed), t)``."""
    base = jax.random.PRNGKey(seed)

    def step(carry, xs):
        t, lam = xs
        cnt = jax.random.poisson(jax.random.fold_in(base, t), lam)
        return carry, cnt.astype(jnp.float32)

    ticks = jnp.arange(lam_t.shape[0], dtype=jnp.int32)
    return jax.lax.scan(step, None, (ticks, lam_t))[1]


@pytest.mark.parametrize("lam,wl", [(lam, None) for lam in
                                    (0.0, 2.0, 6.0, 10.0, 60.0, 90.0, 225.0)]
                         + [(6.0, ONOFF)])
def test_hoisted_arrivals_equal_per_tick_draws_bitwise(lam, wl):
    """The pin behind the byte-identical fig 6-9 artifacts: the point's
    arrival table drawn before the scan equals, bit for bit, the per-tick
    draw inside a scan, across the sampler's zero, Knuth (λ < 10) and
    rejection (λ ≥ 10) branches, and with a windowed rate table."""
    cfg = SMRConfig(sim_seconds=1.0)
    n_ticks = netsim.sim_ticks(cfg)
    if wl is None:
        mode, wlt = workload.TRIVIAL_MODE, None
        lam_t = np.full((n_ticks, N), lam, np.float32)
    else:
        tab = lower(cfg, wl)
        mode = mode_of([tab])
        assert not mode.trivial and not mode.closed
        wlt = {k: jnp.asarray(tab[k]) for k in ("rate_of", "win_of_tick")}
        lam_t = np.float32(lam) * tab["rate_of"][tab["win_of_tick"]]
        assert set(np.unique(lam_t)) == {0.0, np.float32(lam * 2.5)}
    hoisted = jax.jit(lambda seed: workload.draw_arrivals(
        jax.random.PRNGKey(seed), n_ticks, jnp.float32(lam), N, wlt, mode))
    in_scan = jax.jit(_draws_in_scan)
    for seed in (0, 7, 2**31 - 1):
        a = np.asarray(hoisted(seed))
        assert a.shape == (n_ticks, N) and a.dtype == np.float32
        np.testing.assert_array_equal(a, np.asarray(in_scan(seed, lam_t)))
    assert (a.sum() > 0) == (lam > 0)


def _count_eqns(jaxpr, name):
    """Equations of primitive ``name`` in a jaxpr, nested ones included."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == name
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, jcore.ClosedJaxpr):
                    n += _count_eqns(sub.jaxpr, name)
                elif isinstance(sub, jcore.Jaxpr):
                    n += _count_eqns(sub, name)
    return n


@pytest.mark.parametrize("protocol", ["mandator-sporades", "multipaxos"])
@pytest.mark.parametrize("wl,in_scan", [(None, 0), (ONOFF, 0),
                                        (CLOSED, 2)])
def test_open_loop_sampler_sits_before_the_tick_scan(protocol, wl, in_scan):
    """The Poisson sampler's two data-dependent ``while`` loops (Knuth's
    and the rejection sampler's) run once before the scan in the open
    modes, and inside the scan's body, once a tick, in closed mode."""
    cfg = SMRConfig(sim_seconds=0.2)
    _, cfg, mode, env_b, wl_b, rate_b, seed_b, _ = experiment._lower(
        cfg, SweepSpec(rates=(20_000,), workloads=(wl,)), canonical=False)
    assert mode.closed == (in_scan > 0)
    jaxpr = jax.make_jaxpr(lambda env, wlt, rate, seed: harness._scan_body(
        protocol, cfg, netsim.sim_ticks(cfg), rate, env, seed, wlt, mode))(
        *jax.tree.map(lambda x: x[0], (env_b, wl_b, rate_b, seed_b))).jaxpr
    (scan,) = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    assert _count_eqns(jaxpr, "while") == 2
    assert _count_eqns(scan.params["jaxpr"].jaxpr, "while") == in_scan


def test_programs_count_their_arrival_path():
    """``timing_stats()`` counts each program built by where it draws the
    arrivals: before the scan (open loop) or in it (closed loop)."""
    cfg = SMRConfig(sim_seconds=0.3)
    experiment.reset_timing_stats()
    run_sweep("multipaxos", cfg, SweepSpec(rates=(20_000,)))
    st = experiment.timing_stats()["multipaxos"]
    assert (st["arrivals_hoisted"], st["arrivals_in_scan"]) == (1, 0)
    run_sweep("multipaxos", cfg, SweepSpec(
        rates=(20_000,), workloads=(CLOSED,)))
    st = experiment.timing_stats()["multipaxos"]
    assert (st["arrivals_hoisted"], st["arrivals_in_scan"]) == (1, 1)


def test_closed_loop_inflight_never_exceeds_cap():
    cfg = SMRConfig(sim_seconds=1.0)
    wl = Workload("tight", (ClosedLoop(think_ms=20.0, cap=64.0),))
    r = run_sim("mandator-sporades", cfg, rate_tx_s=200_000, workload=wl)
    assert np.all(np.asarray(r["inflight_max"]) <= 64.0 + 1e-6), \
        r["inflight_max"]
    # the cap binds under this load (the pool saturates, not idles)
    assert np.asarray(r["inflight_max"]).max() == pytest.approx(64.0)
    # Little's law: committed throughput can't exceed the cap's bound
    assert r["throughput"] <= N * 64.0 / (r["median_ms"] / 1000.0) * 1.5


def test_closed_loop_feedback_throttles_offered_load():
    """A closed pool submits less than its open-loop twin at the same
    sweep rate once latency eats into the think-time budget."""
    cfg = SMRConfig(sim_seconds=1.0)
    closed = run_sim("mandator-sporades", cfg, rate_tx_s=100_000,
                     workload=library.get("closed-loop", 1.0, N))
    open_ = run_sim("mandator-sporades", cfg, rate_tx_s=100_000)
    assert closed["committed"] < open_["committed"]
    assert closed["throughput"] > 0


def test_region_skew_reports_per_origin_latency():
    cfg = SMRConfig(sim_seconds=1.0)
    r = run_sim("mandator-sporades", cfg, rate_tx_s=50_000,
                workload=Workload("skew", (RegionSkew(hot_frac=0.8,
                                                      hot=(0,)),)))
    med = np.asarray(r["origin_median_ms"])
    assert med.shape == (N,)
    assert np.isfinite(med[0])  # the hot origin definitely committed
    assert r["origin_timeline"].shape[0] == N
    # the hot origin carries most of the committed load
    per_origin = np.asarray(r["origin_timeline"]).sum(axis=1)
    assert per_origin[0] > 0.5 * per_origin.sum()


# ------------------------------------------- batched sweep + baselines ----

def test_workload_grid_is_one_compiled_program_and_matches_sequential():
    """workload × scenario × rate grid through run_sweep: ONE trace per
    protocol, every point bitwise-equal to its single run_sim — including
    open-loop lanes sharing a program with closed-loop lanes."""
    cfg = SMRConfig(sim_seconds=1.0)
    scen = scenario_library.scenarios(cfg.sim_seconds, N)
    wls = (None, library.get("onoff-burst", cfg.sim_seconds, N),
           library.get("closed-loop", cfg.sim_seconds, N))
    spec = SweepSpec(rates=(10_000, 30_000),
                     scenarios=(scen["baseline"], scen["paper-ddos"]),
                     workloads=wls)
    experiment.reset_trace_counts()
    grid = run_sweep("mandator-sporades", cfg, spec)
    # zero traces means an earlier test already compiled the shared
    # canonical program — the one-program claim is the signature count
    assert experiment.trace_counts().get("mandator-sporades", 0) <= 1, \
        "a workload × scenario × rate grid must compile as ONE program"
    assert len(experiment.program_signatures()["mandator-sporades"]) == 1
    assert len(grid) == spec.size == 12
    for r, (rate, seed, fi, wi) in zip(grid, spec.points()):
        single = run_sim("mandator-sporades", cfg, rate_tx_s=rate,
                         scenario=spec.scenarios[fi], seed=seed,
                         workload=wls[wi])
        _assert_point_equal(r, single)


def test_analytic_baselines_consume_workload_tables():
    cfg = SMRConfig(sim_seconds=5.0)
    # patient pools: a long think time keeps the Little's-law equilibrium
    # rate above the models' full-batch formation threshold (they form no
    # partial batches — the same sub-threshold collapse their open-loop
    # curves show at low rates)
    patient = Workload("patient", (ClosedLoop(think_ms=2000.0, cap=1e6),))
    for proto, rate in (("epaxos", 8_000), ("rabia", 2_000)):
        base = run_sweep(proto, cfg, SweepSpec(rates=(rate,)))[0]
        burst = run_sweep(proto, cfg, SweepSpec(
            rates=(rate,),
            workloads=(library.get("onoff-burst", cfg.sim_seconds, N),)))[0]
        closed = run_sweep(proto, cfg, SweepSpec(
            rates=(rate,), workloads=(patient,)))[0]
        assert base["workload"] == "poisson-open"
        assert burst["workload"] == "onoff-burst"
        assert base["throughput"] > 0
        assert closed["throughput"] > 0
        # bursty traffic changes the model's answer (table is read)
        assert burst["committed"] != base["committed"]
        # closed loop can't commit more than the open offered rate
        assert closed["committed"] <= base["committed"] + 1e-6


def test_fault_schedule_is_removed():
    """The deprecated seed-era shim is gone (deprecated in PR 3,
    removed in PR 5) — new callers pass Scenarios to run_sweep/run_sim."""
    from repro.core import netsim
    assert not hasattr(netsim, "FaultSchedule")
