"""SMR simulation harness: drives a protocol over the WAN sim and produces
the paper's metrics (throughput, median/p99 execution latency, timelines).

Protocols:
  mandator-sporades  — Alg 1 + Algs 2/3 (full tick-level state machines)
  mandator-paxos     — Alg 1 + Multi-Paxos ordering the vector clock
  multipaxos         — monolithic Multi-Paxos (batches inside consensus)
  mandator           — dissemination layer alone (completion throughput)
  epaxos / rabia     — analytic baselines (see docstrings in epaxos.py/rabia.py)

Everything here is traceable end-to-end: ``sim_point`` runs the tick-level
``jax.lax.scan`` AND extracts the metrics on-device (searchsorted commit
reconstruction, weighted quantiles, timeline histogram), so the batched
experiment engine (core/experiment.py) can ``jax.vmap`` a whole
rate × seed × fault grid into one compiled program. ``run_sim`` is a thin
single-point wrapper over that engine, kept for backward compatibility.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.smr import SMRConfig
from repro.core import channel as ch
from repro.core import mandator, netsim, paxos, sporades, workload
from repro.distributed import sketch as dsketch
from repro.obs import monitor as hmon
from repro.obs import trace as obs
from repro.workloads.compile import TRIVIAL_MODE, WorkloadMode

SCAN_PROTOCOLS = ("mandator-sporades", "mandator-paxos", "multipaxos",
                  "mandator")


def _closed_feedback(protocol: str, carry: Dict, out: Dict) -> Dict:
    """Closed-loop commit feedback, inside the scan carry: a request is in
    flight from Poisson submission until the batch carrying it commits.
    ``cl_done`` is the cumulative per-origin committed request count,
    recovered from the batch records and the protocol's committed rounds
    (both monotone, so no per-round event bookkeeping is needed)."""
    wl_key = "p" if protocol == "multipaxos" else "m"
    carry = dict(carry)
    carry[wl_key] = dict(carry[wl_key])
    wl = dict(carry[wl_key]["wl"])
    if protocol == "mandator":
        cvc_o = carry["m"]["own_round"]
    elif protocol == "mandator-sporades":
        cvc_o = jnp.max(carry["s"]["cvc"], axis=0)
    elif protocol == "mandator-paxos":
        cvc_o = jnp.max(carry["p"]["cvc"], axis=0)
    else:
        cvc_o = carry["p"]["committed_slot"]
    # cumulative committed count = the prefix sum at the committed round
    # (rounds are formed and committed in order per row)
    r_max = wl["batch_count_cum"].shape[1]
    n = cvc_o.shape[0]
    done = wl["batch_count_cum"][jnp.arange(n),
                                 jnp.clip(cvc_o, 0, r_max - 1)]
    if protocol == "multipaxos":
        # batch rows live at the (rotating) leader, not the submitting
        # origin, so per-origin completion is unknowable: apportion the
        # global committed total (monotone) pro-rata by cumulative
        # submissions. The aggregate is exact; the per-origin split is an
        # estimate that may move as shares shift, so no per-origin
        # ratchet — a maximum here would overcount done and silently
        # admit requests past the cap. (Requests forwarded to a dead
        # leader stay in flight; client retry is not modeled, DESIGN.md §8.)
        share = wl["cl_submitted"] / jnp.maximum(
            jnp.sum(wl["cl_submitted"]), 1.0)
        done = jnp.sum(done) * share
        wl["cl_done"] = jnp.clip(done, 0.0, wl["cl_submitted"])
    else:
        wl["cl_done"] = jnp.clip(jnp.maximum(wl["cl_done"], done),
                                 0.0, wl["cl_submitted"])
    carry[wl_key]["wl"] = wl
    out["inflight"] = wl["cl_submitted"] - wl["cl_done"]
    return carry


def ring_specs(protocol: str, n: int) -> List[ch.RingSpec]:
    """The packed rings a point of ``protocol`` carries, in carry order
    (Mandator's ``m``, then Sporades' ``s`` or Paxos' ``p``)."""
    specs = []
    if protocol in ("mandator-sporades", "mandator-paxos", "mandator"):
        specs.append(mandator.ring_spec())
    if protocol == "mandator-sporades":
        specs.append(sporades.ring_spec(n))
    if protocol in ("mandator-paxos", "multipaxos"):
        specs.append(paxos.ring_spec(n, protocol == "mandator-paxos"))
    return specs


def _monitor_views(protocol: str, cfg: SMRConfig, carry: Dict) -> Dict:
    """Protocol-state projection the health monitor consumes
    (repro.obs.monitor.update): per-replica committed vector clocks /
    monotone commit keys / views where the protocol has them (None keys
    statically compile the corresponding check out), per-origin formed vs
    stable rounds (the starvation gauge), a cluster commit total (the
    watchdog's progress signal), a pending-work flag, packed-ring
    occupancy, and the per-tick dropped-send counts the ticks stash in
    ``mon_io``."""
    n = cfg.n_replicas
    views: Dict = {"cvc": None, "commit_seq": None, "view": None}
    dropped = jnp.zeros((n,), jnp.int32)
    if protocol in ("mandator-sporades", "mandator-paxos", "mandator"):
        m = carry["m"]
        dropped = dropped + m["mon_io"]["dropped"]
        views["formed"] = m["formed_round"]
        views["stable"] = m["own_round"]
        pending = jnp.sum(m["wl"]["buffer"]) > 0
    if protocol == "mandator":
        # lcr rows are per-replica *knowledge* vectors — cross-replica
        # comparability is not an invariant of dissemination alone, so no
        # cvc here (no agreement check); completion order still is one.
        views["commit_seq"] = m["own_round"]
        views["commit_tot"] = jnp.sum(m["own_round"]).astype(jnp.float32)
        views["pending"] = pending | jnp.any(
            m["formed_round"] > m["own_round"])
    elif protocol == "mandator-sporades":
        s = carry["s"]
        dropped = dropped + s["mon_io"]["dropped"]
        views["cvc"] = s["cvc"]
        views["commit_seq"] = s["commit_key"]
        views["view"] = s["v_cur"]
        views["commit_tot"] = jnp.sum(s["cvc"]).astype(jnp.float32)
        views["pending"] = pending | jnp.any(
            m["formed_round"] > jnp.max(s["cvc"], axis=0))
    elif protocol == "mandator-paxos":
        p = carry["p"]
        dropped = dropped + p["mon_io"]["dropped"]
        views["cvc"] = p["cvc"]
        views["view"] = p["view"]
        views["commit_tot"] = jnp.sum(p["cvc"]).astype(jnp.float32)
        views["pending"] = pending | jnp.any(
            m["formed_round"] > jnp.max(p["cvc"], axis=0))
    elif protocol == "multipaxos":
        p = carry["p"]
        dropped = dropped + p["mon_io"]["dropped"]
        # per-replica slot counters are each leader's own ledger: formed
        # (last started) vs stable (last committed) per replica
        views["formed"] = p["slot"]
        views["stable"] = p["committed_slot"]
        views["commit_seq"] = p["committed_slot"]
        views["view"] = p["view"]
        views["commit_tot"] = jnp.sum(
            p["committed_slot"]).astype(jnp.float32)
        views["pending"] = (jnp.sum(p["wl"]["buffer"]) > 0) \
            | jnp.any(p["outstanding"])
    rings = [carry[k]["ring"] for k in ("m", "s", "p") if k in carry]
    occ = [ch.ring_occupancy(spec, ring)
           for spec, ring in zip(ring_specs(protocol, n), rings)]
    views["ring_occ"] = occ[0] if len(occ) == 1 else jnp.maximum(*occ)
    views["dropped"] = dropped
    return views


def hoists_arrivals(mode: WorkloadMode) -> bool:
    """Whether a program of this workload mode draws its arrivals before
    the tick scan (open loop) rather than tick by tick inside it (closed
    loop, whose mean depends on the requests in flight)."""
    return not mode.closed


def _scan_body(protocol: str, cfg: SMRConfig, n_ticks: int,
               rate_per_tick: jax.Array, env: Dict, seed: jax.Array,
               wlt: Dict | None = None,
               mode: WorkloadMode = TRIVIAL_MODE):
    """The tick loop. protocol/cfg/n_ticks/mode are static; rate_per_tick,
    env and wlt leaves, and seed may be traced (and batched by vmap)."""
    uses_mandator = protocol in ("mandator-sporades", "mandator-paxos",
                                 "mandator")
    st = {}
    if uses_mandator:
        st["m"] = mandator.init_state(cfg, n_ticks, closed=mode.closed)
    if protocol == "mandator-sporades":
        st["s"] = sporades.init_state(cfg, n_ticks)
    if protocol in ("mandator-paxos", "multipaxos"):
        st["p"] = paxos.init_state(cfg, n_ticks,
                                   mandator_mode=(protocol == "mandator-paxos"),
                                   closed=mode.closed)
    # health monitor (repro.obs.monitor): absent from the carry at the
    # default monitor_level="off" — the compiled program is then
    # instruction-identical to an unmonitored build, like trace_level
    mon_on = hmon.on(cfg.monitor_level)
    if mon_on:
        st["mon"] = hmon.init_monitor(cfg, n_ticks,
                                      _monitor_views(protocol, cfg, st))
        grace = hmon.stall_grace_ticks(cfg, env)
    base_key = jax.random.PRNGKey(seed)
    ticks = jnp.arange(n_ticks, dtype=jnp.int32)
    # open-loop arrivals depend on nothing in the carry: draw the point's
    # whole table before the scan, one row a tick (workload.py)
    hoisted = hoists_arrivals(mode)
    rows = (workload.draw_arrivals(base_key, n_ticks, rate_per_tick,
                                   cfg.n_replicas, wlt, mode)
            if hoisted else None)

    def step(carry, xs):
        t, row = xs
        draw = row if hoisted else jax.random.fold_in(base_key, t)
        out = {}
        if uses_mandator:
            carry = dict(carry)
            carry["m"] = mandator.tick(carry["m"], t, draw, env, cfg,
                                       rate_per_tick, wlt, mode)
            lcr = mandator.get_client_requests(carry["m"])
            out["own_round"] = carry["m"]["own_round"]
        if protocol == "mandator-sporades":
            carry["s"] = sporades.tick(carry["s"], t, env, cfg, lcr)
            out["cvc"] = jnp.max(carry["s"]["cvc"], axis=0)
            out["cvc_all"] = carry["s"]["cvc"]
            out["commit_key"] = carry["s"]["commit_key"]
            out["is_async"] = carry["s"]["is_async"]
            out["v_cur"] = carry["s"]["v_cur"]
        elif protocol == "mandator-paxos":
            carry["p"] = paxos.tick(carry["p"], t, None, env, cfg,
                                    rate_per_tick, True, lcr=lcr)
            out["cvc"] = jnp.max(carry["p"]["cvc"], axis=0)
            if cfg.trace_level != obs.TraceLevel.OFF:
                # each origin's OWN committed-VC observation — the
                # delivery-phase boundary (sporades reads it off the
                # cvc_all trace it already emits; off => compiled out)
                out["cvc_own"] = jnp.diagonal(carry["p"]["cvc"])
        elif protocol == "multipaxos":
            carry = dict(carry)
            carry["p"] = paxos.tick(carry["p"], t, draw, env, cfg,
                                    rate_per_tick, False, wlt=wlt, mode=mode)
            out["committed_slot"] = carry["p"]["committed_slot"]
        if mode.closed:
            carry = _closed_feedback(protocol, carry, out)
        if mon_on:
            carry = dict(carry)
            carry["mon"] = hmon.update(
                carry["mon"], t, cfg, env,
                _monitor_views(protocol, cfg, carry), grace, wlt=wlt,
                inflight=out.get("inflight"),
                # multipaxos closed-loop completion is a pro-rata estimate
                # (see _closed_feedback), not an exact per-origin count —
                # the cap invariant is only checkable where done is exact
                check_cap=mode.closed and protocol != "multipaxos")
        return carry, out

    st, trace = jax.lax.scan(step, st, (ticks, rows))
    return st, trace


def _weighted_quantile(vals: jax.Array, weights: jax.Array, q: float
                       ) -> jax.Array:
    """On-device weighted quantile over flat arrays; zero-weight entries are
    inert (they only flatten the CDF) so no boolean filtering is needed."""
    order = jnp.argsort(vals)
    v, w = vals[order], weights[order]
    cum = jnp.cumsum(w)
    tot = cum[-1]
    # guard the denominator, not just the result: an empty window would
    # otherwise divide by zero before the where (trips jax_debug_nans)
    cdf = cum / jnp.where(tot > 0, tot, 1.0)
    idx = jnp.clip(jnp.searchsorted(cdf, q, side="left"),
                   0, v.shape[0] - 1)
    return jnp.where(tot > 0, v[idx], jnp.nan)


def _batch_metrics(cfg: SMRConfig, create_t, arr_mean, count, commit_t,
                   warmup_frac=0.15, bucket_ms=500.0) -> Dict:
    """Metrics over batch records [n, R] (ticks -> ms via cfg.tick_ms),
    fully on-device so it vmaps across grid points."""
    n_ticks = netsim.sim_ticks(cfg)
    ok = jnp.isfinite(commit_t) & (count > 0) & jnp.isfinite(create_t)
    lat_ms = (commit_t - arr_mean) * cfg.tick_ms
    w0 = warmup_frac * n_ticks
    in_win = ok & (commit_t >= w0)
    win_s = (n_ticks - w0) * cfg.tick_ms / 1000.0
    w = jnp.where(in_win, count, 0.0).ravel()
    tput = jnp.sum(w) / win_s if win_s > 0 else jnp.float32(0.0)
    med = _weighted_quantile(lat_ms.ravel(), w, 0.5)
    p99 = _weighted_quantile(lat_ms.ravel(), w, 0.99)
    nbuck = int(np.ceil(n_ticks * cfg.tick_ms / bucket_ms))
    b = jnp.where(ok, commit_t * (cfg.tick_ms / bucket_ms), 0.0
                  ).astype(jnp.int32).clip(0, nbuck - 1)
    cnt_ok = jnp.where(ok, count, 0.0)
    timeline = jnp.zeros((nbuck,)).at[b.ravel()].add(cnt_ok.ravel())
    timeline = timeline / (bucket_ms / 1000.0)
    # per-origin client-perceived latency: where is the latency paid?
    # (rows are submitting origins for the mandator-family protocols;
    # for multipaxos they are the leader that formed the slot batch)
    n = count.shape[0]
    w_o = jnp.where(in_win, count, 0.0)                       # [n, R]
    med_o = jax.vmap(lambda v, ww: _weighted_quantile(v, ww, 0.5))(
        lat_ms, w_o)
    p99_o = jax.vmap(lambda v, ww: _weighted_quantile(v, ww, 0.99))(
        lat_ms, w_o)
    rows = jnp.broadcast_to(jnp.arange(n)[:, None], b.shape)
    tl_o = jnp.zeros((n, nbuck)).at[rows, b].add(cnt_ok)
    lat_sum = jnp.zeros((n, nbuck)).at[rows, b].add(
        cnt_ok * jnp.where(ok, lat_ms, 0.0))
    lat_tl_o = jnp.where(tl_o > 0, lat_sum / jnp.maximum(tl_o, 1e-9),
                         jnp.nan)
    return {"throughput": tput, "median_ms": med, "p99_ms": p99,
            "timeline": timeline,
            "committed": jnp.sum(cnt_ok),
            "origin_median_ms": med_o, "origin_p99_ms": p99_o,
            "origin_timeline": tl_o / (bucket_ms / 1000.0),
            "origin_lat_ms_timeline": lat_tl_o}


# Per-batch / per-tick output arrays whose size scales with the grid's
# record capacity — the ones the sharded sweep path (experiment.py) trades
# for the O(SKETCH_BINS) latency sketch so a 10^4-point grid returns
# O(sketch) bytes per point. Scalar metrics are untouched: ``reduced``
# mode computes them with the IDENTICAL op sequence (the heavy keys are
# simply not program outputs, so XLA dead-code-eliminates their compute).
REDUCED_DROPS = ("timeline", "origin_median_ms", "origin_p99_ms",
                 "origin_timeline", "origin_lat_ms_timeline",
                 "cvc_all", "commit_key",
                 "batch_marks_t", "batch_arr_t", "batch_n")


def _latency_sketch(cfg: SMRConfig, create_t, arr_mean, count, commit_t,
                    warmup_frac=0.15) -> Dict:
    """Fixed-size on-device digest of the committed-latency distribution,
    over the same measurement window / weights as ``_batch_metrics``
    (duplicated ops CSE away under jit)."""
    n_ticks = netsim.sim_ticks(cfg)
    ok = jnp.isfinite(commit_t) & (count > 0) & jnp.isfinite(create_t)
    lat_ms = (commit_t - arr_mean) * cfg.tick_ms
    in_win = ok & (commit_t >= warmup_frac * n_ticks)
    w = jnp.where(in_win, count, 0.0).ravel()
    # zero-weight rows may hold inf/nan latencies (uncommitted batches);
    # dsketch.build masks them instead of multiplying through
    return dsketch.build(lat_ms.ravel(), w)


def _vc_commit_ticks(cvc_trace: jax.Array, r_max: int) -> jax.Array:
    """cvc_trace: [ticks, n] monotone. Returns [n, r_max] where column r is
    the commit tick of batch (k, r); rounds are 1-based so column 0 is inf,
    and inf marks rounds that never commit."""
    ticks = cvc_trace.shape[0]
    rs = jnp.arange(r_max)

    def per_origin(col):
        idx = jnp.searchsorted(col, rs, side="left")
        valid = (idx < ticks) & (rs >= 1)
        return jnp.where(valid, idx.astype(jnp.float32), jnp.inf)

    return jax.vmap(per_origin, in_axes=1)(cvc_trace)


def sim_point(protocol: str, cfg: SMRConfig, env: Dict,
              rate_per_tick: jax.Array, seed: jax.Array,
              wlt: Dict | None = None,
              mode: WorkloadMode = TRIVIAL_MODE,
              reduced: bool = False) -> Dict:
    """One grid point, traceable end-to-end: tick scan + on-device metric
    extraction. Returns a dict of arrays (scalars unless noted). ``wlt``
    is the compiled workload table (ignored when mode.trivial); ``mode``
    is static and must match how wlt was compiled.

    ``reduced`` (static) is the sharded sweep engine's metric contract:
    scalar metrics keep the exact unreduced op sequence (bitwise-equal
    values), the per-batch/per-tick arrays in ``REDUCED_DROPS`` are
    omitted, and a fixed-size latency ``sketch`` is added in their place
    so each point returns O(SKETCH_BINS) bytes of distribution.

    Everything after the scan runs under the ``extract`` named scope."""
    n_ticks = netsim.sim_ticks(cfg)
    st, trace = _scan_body(protocol, cfg, n_ticks, rate_per_tick, env, seed,
                           wlt, mode)
    # metric extraction: everything after the scan, named for the trace
    with jax.named_scope("extract"):
        if protocol == "mandator":
            # dissemination completion = "commit" for availability accounting
            wl, cvc = st["m"]["wl"], trace["own_round"]
        elif protocol in ("mandator-sporades", "mandator-paxos"):
            # batch r commits once the committed VC reaches r (1-based rounds)
            wl, cvc = st["m"]["wl"], trace["cvc"]
        elif protocol == "multipaxos":
            wl, cvc = st["p"]["wl"], trace["committed_slot"]
        else:
            raise ValueError(protocol)
        commit_t = _vc_commit_ticks(cvc, wl["batch_count"].shape[1])
        out = _batch_metrics(cfg, wl["batch_create_t"], wl["batch_arr_mean"],
                             wl["batch_count"], commit_t)
        if protocol == "mandator-sporades":
            out["async_frac"] = jnp.mean(trace["is_async"].astype(jnp.float32))
            out["views"] = jnp.max(trace["v_cur"])
            out["cvc_all"] = trace["cvc_all"]          # [ticks, n, n]
            out["commit_key"] = trace["commit_key"]    # [ticks, n]
        if mode.closed:
            out["inflight_max"] = jnp.max(trace["inflight"], axis=0)   # [n]
        if cfg.trace_level != obs.TraceLevel.OFF:
            out.update(_phase_breakdown(protocol, cfg, wl, trace, commit_t,
                                        n_ticks))
            rings = {layer: obs.public_view(st[k].get("tr"))
                     for k, layer in (("m", "mandator"), ("s", "sporades"),
                                      ("p", "paxos")) if k in st}
            out["obs"] = {k: v for k, v in rings.items() if v is not None}
        if hmon.on(cfg.monitor_level):
            out["mon"] = hmon.public_view(st["mon"], n_ticks)
        if reduced:
            out = {k: v for k, v in out.items() if k not in REDUCED_DROPS}
            out["sketch"] = _latency_sketch(
                cfg, wl["batch_create_t"], wl["batch_arr_mean"],
                wl["batch_count"], commit_t)
        return out


def _phase_breakdown(protocol: str, cfg: SMRConfig, wl: Dict, trace: Dict,
                     commit_t: jax.Array, n_ticks: int,
                     warmup_frac: float = 0.15) -> Dict:
    """Latency-breakdown accounting (repro.obs.PHASES): split each
    committed batch's end-to-end latency at three protocol boundaries —
    batch creation at the origin (queue | dissemination), stability
    (n-f dissemination votes; dissemination | consensus), and global
    commit (consensus | delivery, the origin's own observation). The
    four phase marks telescope back to the client-perceived latency of
    ``_batch_metrics`` exactly (± nothing: same arrival mean, same
    commit reconstruction), pinned by tests/test_obs.py."""
    r_max = wl["batch_count"].shape[1]
    create_t, arr_t = wl["batch_create_t"], wl["batch_arr_mean"]
    cnt = wl["batch_count"]
    if protocol == "mandator":
        # dissemination IS the protocol: completion == commit == delivery
        stable_t = deliv_t = commit_t
    elif protocol in ("mandator-sporades", "mandator-paxos"):
        # stability = the origin's own chain completing the round
        stable_t = _vc_commit_ticks(trace["own_round"], r_max)
        own_cvc = (jnp.diagonal(trace["cvc_all"], axis1=1, axis2=2)
                   if protocol == "mandator-sporades" else trace["cvc_own"])
        deliv_t = _vc_commit_ticks(own_cvc, r_max)
    else:  # multipaxos: monolithic — the slot batch enters consensus as
        # it forms, and commit is observed at the committing leader
        stable_t = create_t
        deliv_t = commit_t
    marks = jnp.stack([create_t, stable_t, commit_t, deliv_t])  # [4, n, R]
    prev = jnp.stack([arr_t, create_t, stable_t, commit_t])
    phases_ms = jnp.maximum(marks - prev, 0.0) * cfg.tick_ms
    ok = jnp.isfinite(marks).all(axis=0) & (cnt > 0)
    in_win = ok & (commit_t >= warmup_frac * n_ticks)   # same window as
    w = jnp.where(in_win, cnt, 0.0)                     # _batch_metrics
    glob = jax.vmap(lambda v, q: _weighted_quantile(v.ravel(), w.ravel(), q),
                    in_axes=(0, None))
    origin = jax.vmap(jax.vmap(_weighted_quantile, in_axes=(0, 0, None)),
                      in_axes=(0, None, None))
    out = {"phase_med_ms": glob(phases_ms, 0.5),             # [4]
           "phase_p99_ms": glob(phases_ms, 0.99),
           "phase_origin_med_ms": origin(phases_ms, w, 0.5),  # [4, n]
           "phase_origin_p99_ms": origin(phases_ms, w, 0.99)}
    if cfg.trace_level == obs.TraceLevel.FULL:
        out["batch_marks_t"] = marks      # absolute ticks, inf = never
        out["batch_arr_t"] = arr_t
        out["batch_n"] = cnt
    return out


def run_sim(protocol: str, cfg: SMRConfig, rate_tx_s: float,
            scenario=None, seed: int = 0, workload=None,
            canonical: bool = True) -> Dict:
    """Single-point wrapper over the batched engine (experiment.run_sweep).
    scenario: a repro.scenarios.Scenario (or None for fault-free).
    workload: a repro.workloads.Workload (or None for the §5.2 baseline).
    ``canonical`` (default) pads to the canonical program signature, so
    repeated single points — and the fig-suite sweeps — all reuse ONE
    compiled program per protocol instead of compiling a B=1 variant."""
    from repro.core.experiment import SweepSpec, run_sweep
    spec = SweepSpec(rates=(float(rate_tx_s),), seeds=(int(seed),),
                     scenarios=(scenario,), workloads=(workload,))
    return run_sweep(protocol, cfg, spec, canonical=canonical)[0]
