"""Batched experiment engine (core/experiment.py): a vmapped sweep grid must
compile at most once per protocol (never per grid point) and produce
bitwise-identical metrics to the equivalent sequence of single run_sim
calls (same seeds/scenarios)."""
import jax
import numpy as np
import pytest

from repro.configs.smr import SMRConfig
from repro.core import experiment
from repro.core.experiment import SweepSpec, run_sweep
from repro.core.harness import run_sim
from repro.scenarios import Crash, Scenario, TargetedDelay

CFG = SMRConfig(sim_seconds=1.0)
SCALARS = ("throughput", "median_ms", "p99_ms", "committed")


def _assert_point_equal(batched, single):
    for k in SCALARS:
        b, s = batched[k], single[k]
        assert (b == s) or (np.isnan(b) and np.isnan(s)), \
            f"{k}: batched {b} != sequential {s}"
    np.testing.assert_array_equal(batched["timeline"], single["timeline"])


@pytest.mark.parametrize("protocol", ["mandator-sporades", "multipaxos"])
def test_grid_matches_sequential_run_sim(protocol):
    """Fig-6-style grid (3 rates x 2 seeds) through one vmapped dispatch ==
    six sequential single-point runs, bit for bit."""
    spec = SweepSpec(rates=(10_000, 20_000, 40_000), seeds=(0, 1))
    experiment.reset_trace_counts()
    grid = run_sweep(protocol, CFG, spec)
    # 0 = this shape's canonical program was already built earlier in the
    # process (the program store shares it); the guarantee under test is
    # that a grid NEVER builds one program per point
    assert experiment.trace_counts().get(protocol, 0) <= 1, \
        "a whole grid must compile as at most ONE program"
    assert len(grid) == spec.size == 6
    for r, (rate, seed, _, _) in zip(grid, spec.points()):
        assert (r["rate"], r["seed"]) == (rate, seed)
        _assert_point_equal(r, run_sim(protocol, CFG, rate_tx_s=rate,
                                       seed=seed))


def test_scenario_variants_stack_into_one_program():
    """Heterogeneous scenarios (none / crash / DDoS) batch through the
    stacked-env path and still match their single-point runs. The DDoS
    variant also forces the sweep-wide auto horizon (1024 >> the crash
    variants' standalone bound), so this pins that a shared ring size
    keeps every point bitwise equal to its own single run."""
    scenarios = (None,
                 Scenario("crash", (Crash(start_s=0.5, targets=(0,)),)),
                 Scenario("ddos", (TargetedDelay(
                     delay_ms=800.0, targets="random-minority",
                     repick_s=0.5, seed=7),)))
    spec = SweepSpec(rates=(20_000,), scenarios=scenarios)
    experiment.reset_trace_counts()
    grid = run_sweep("mandator-sporades", CFG, spec)
    assert experiment.trace_counts().get("mandator-sporades", 0) <= 1
    for r, (rate, seed, fi, _) in zip(grid, spec.points()):
        single = run_sim("mandator-sporades", CFG, rate_tx_s=rate,
                         scenario=scenarios[fi], seed=seed)
        _assert_point_equal(r, single)
        np.testing.assert_array_equal(r["cvc_all"], single["cvc_all"])


def test_analytic_baselines_share_the_sweep_api():
    rows = run_sweep("epaxos", SMRConfig(sim_seconds=5.0),
                     SweepSpec(rates=(5_000, 10_000)))
    assert [r["rate"] for r in rows] == [5_000, 10_000]
    assert rows[1]["throughput"] > 0


def test_unknown_protocol_rejected():
    with pytest.raises(ValueError):
        run_sweep("zab", CFG, SweepSpec(rates=(1_000,)))


def test_host_spans_count_once_per_grid_and_feed_compile_and_run():
    """One grid enters each host span once, and ``compile_s``/``run_s``
    are made of the same span timings: the enqueue (in one bucket or the
    other, by whether it traced) and the readback."""
    experiment.reset_timing_stats()
    run_sweep("multipaxos", CFG, SweepSpec(rates=(20_000, 40_000)))
    st = experiment.timing_stats()["multipaxos"]
    assert st["dispatches"] == 1
    for name in experiment.SPANS:
        assert st[f"{name}_n"] == 1, name
        assert st[f"{name}_s"] > 0.0, name
    assert st["compile_s"] + st["run_s"] == pytest.approx(
        st["enqueue_s"] + st["readback_s"], rel=1e-12, abs=0.0)
    assert st["compile_s"] in (0.0, st["enqueue_s"])


def test_host_spans_are_annotations_on_the_profiler_clock(tmp_path):
    """The spans reach a profiler trace as ``experiment.<span>`` host
    events, nested in the order a grid runs them."""
    import jax
    from jax.profiler import ProfileData
    spec = SweepSpec(rates=(20_000,))
    run_sweep("multipaxos", CFG, spec)
    with jax.profiler.trace(str(tmp_path)):
        run_sweep("multipaxos", CFG, spec)
    (path,) = tmp_path.rglob("*.xplane.pb")
    events = sorted((e.start_ns, e.name)
                    for p in ProfileData.from_file(str(path)).planes
                    if p.name.startswith("/host:")
                    for ln in p.lines for e in ln.events
                    if e.name.startswith("experiment."))
    assert [n for _, n in events] == [f"experiment.{s}"
                                      for s in experiment.SPANS]


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
@pytest.mark.parametrize("n", [1, 2, 3, 16, 17, 20, 256])
def test_lane_chunks_split_the_grid(n, platform):
    """On the TPU a canonical grid runs as chunks of non-increasing powers
    of two, each at most ``LANE_CAP``, that cover it with no padding;
    elsewhere as one one-lane chunk a point."""
    chunks = experiment._lane_chunks(n, platform)
    assert sum(chunks) == n
    if platform == "cpu":
        assert chunks == [1] * n
        return
    assert all(c & (c - 1) == 0 and 1 <= c <= experiment.LANE_CAP
               for c in chunks)
    assert chunks == sorted(chunks, reverse=True)
    assert chunks[0] == min(experiment.LANE_CAP, 1 << (n.bit_length() - 1))


@pytest.mark.parametrize("protocol,n,horizon,width", [
    ("mandator-sporades", 5, 256, 8), ("multipaxos", 5, 256, 8),
    ("mandator-sporades", 5, 1024, 2), ("multipaxos", 5, 1024, 4),
    ("mandator-sporades", 9, 256, 2)])
def test_lane_chunks_keep_a_chunks_rings_in_vmem(protocol, n, horizon,
                                                 width):
    """On the TPU a chunk narrows from ``LANE_CAP`` until its padded rings
    fit ``RING_VMEM_BYTES``: fig 6's programs keep 8 lanes, the 1,024-slot
    and the nine-replica ones fewer."""
    cfg = SMRConfig(n_replicas=n, delay_horizon_ticks=horizon)
    lane = experiment._lane_ring_bytes(protocol, cfg)
    assert experiment._lane_chunks(64, "tpu", lane) == [width] * (64 // width)
    assert width * lane <= experiment.RING_VMEM_BYTES
    assert (width == experiment.LANE_CAP
            or 2 * width * lane > experiment.RING_VMEM_BYTES)


def _assert_rows_bitwise(wide, narrow):
    assert len(wide) == len(narrow)
    for w, s in zip(wide, narrow):
        assert w.keys() == s.keys()
        for k in w:
            lw, ls = jax.tree.leaves(w[k]), jax.tree.leaves(s[k])
            assert len(lw) == len(ls), k
            for a, b in zip(lw, ls):
                a, b = np.asarray(a), np.asarray(b)
                assert (a.dtype, a.shape) == (b.dtype, b.shape), k
                assert a.tobytes() == b.tobytes(), k


@pytest.mark.parametrize("protocol", ["mandator-sporades", "multipaxos"])
def test_multi_lane_chunks_match_one_lane_bitwise(protocol, monkeypatch):
    """A 6-point grid run as a 4-lane and a 2-lane launch (the TPU's
    chunking, forced here) equals its one-lane-a-point run in every output
    of every point, bit for bit, with one program built per width (0.6 s
    simulated: both protocols commit at every rate by then)."""
    cfg = SMRConfig(sim_seconds=0.6)
    spec = SweepSpec(rates=(20_000, 60_000, 120_000), seeds=(3, 4))
    experiment.reset_timing_stats()
    narrow = run_sweep(protocol, cfg, spec)
    assert experiment.timing_stats()[protocol]["by_lanes"] == {"1": 6}

    def chunks(n_points, platform, lane_bytes):
        assert n_points == spec.size
        return [4, 2]
    monkeypatch.setattr(experiment, "_lane_chunks", chunks)
    experiment.reset_trace_counts()
    experiment.reset_timing_stats()
    wide = run_sweep(protocol, cfg, spec)
    assert experiment.trace_counts()[protocol] == 2
    assert experiment.timing_stats()[protocol]["by_lanes"] == {"4": 1,
                                                               "2": 1}
    assert sorted(sig.lanes for sig in
                  experiment.program_signatures()[protocol]) == [2, 4]
    assert all(r["committed"] > 0 for r in narrow)
    if protocol == "mandator-sporades":
        assert "cvc_all" in wide[0] and "commit_key" in wide[0]
    _assert_rows_bitwise(wide, narrow)
