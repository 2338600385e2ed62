"""Client arrivals (open- or closed-loop) + batch bookkeeping.

Arrivals are Poisson per tick per origin. The mean comes from one of two
statically-selected paths (``repro.workloads.WorkloadMode``):

  trivial — the seed-era §5.2 baseline: ``rate_per_tick`` broadcast to all
            origins, instruction-identical to the original scalar path
            (what keeps the fig 6-9 artifacts byte-identical);
  table   — ``rate_per_tick x rate_of[win_of_tick[t]]`` from a compiled
            ``repro.workloads`` rate table; in closed mode the table
            instead sizes geo-placed client pools (Little's law) whose
            submission rate is gated on in-flight requests and capped at
            ``cap`` outstanding per origin.

Batch records are global arrays indexed [origin, round]:
  create_t   — tick when the batch was formed
  arr_mean   — mean arrival tick of its requests (for execution latency)
  count      — number of requests in the batch
Commit times are reconstructed post-hoc from the per-tick committed-VC
trace (searchsorted), so the hot loop never touches [n, R_MAX] arrays.
The closed-loop in-flight decrement at commit lives in the scan step
(harness._scan_body), which owns the commit signal.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.smr import SMRConfig
from repro.workloads.compile import TRIVIAL_MODE, WorkloadMode


def init_workload(cfg: SMRConfig, n_ticks: int,
                  closed: bool = False) -> Dict[str, jax.Array]:
    n = cfg.n_replicas
    wl = {
        "buffer": jnp.zeros((n,), jnp.float32),        # pending request count
        "buffer_tsum": jnp.zeros((n,), jnp.float32),   # sum of arrival ticks
        "last_batch_t": jnp.zeros((n,), jnp.float32),
        "cpu_tokens": jnp.zeros((n,), jnp.float32),
        "batch_create_t": jnp.full((n, n_ticks), jnp.inf, jnp.float32),
        "batch_arr_mean": jnp.zeros((n, n_ticks), jnp.float32),
        "batch_count": jnp.zeros((n, n_ticks), jnp.float32),
    }
    if closed:
        wl["cl_submitted"] = jnp.zeros((n,), jnp.float32)
        wl["cl_done"] = jnp.zeros((n,), jnp.float32)
        # running prefix sum of batch_count by round (written at formation,
        # rounds are formed in order) so the commit feedback is an O(n)
        # gather per tick instead of an O(n x n_ticks) masked reduction
        wl["batch_count_cum"] = jnp.zeros((n, n_ticks), jnp.float32)
    return wl


def arrive(wl: Dict, key: jax.Array, t: jax.Array, rate_per_tick: jax.Array,
           alive: jax.Array, wlt: Optional[Dict] = None,
           mode: WorkloadMode = TRIVIAL_MODE) -> Dict:
    """Poisson arrivals this tick at each origin's clients. ``wlt`` is the
    compiled workload table (required unless mode.trivial). Runs under the
    ``arrivals`` named scope, the layer the device trace reads."""
    with jax.named_scope("arrivals"):
        wl = dict(wl)
        if mode.trivial:
            lam = jnp.broadcast_to(rate_per_tick, alive.shape)
            cnt = jax.random.poisson(key, lam).astype(jnp.float32) * alive
        else:
            mult = wlt["rate_of"][wlt["win_of_tick"][t]]           # [n]
            lam = rate_per_tick * mult
            if mode.closed:
                # pool size via Little's law at the sweep rate; submission is
                # gated on requests still in flight and capped at `cap`
                inflight = wl["cl_submitted"] - wl["cl_done"]
                clients = rate_per_tick * wlt["think_ticks"] * mult
                lam_cl = jnp.clip(clients - inflight, 0.0) / wlt["think_ticks"]
                lam = jnp.where(wlt["closed"] > 0, lam_cl, lam)
            cnt = jax.random.poisson(key, lam).astype(jnp.float32) * alive
            if mode.closed:
                room = jnp.clip(wlt["cap"] - inflight, 0.0)
                cnt = jnp.where(wlt["closed"] > 0, jnp.minimum(cnt, room), cnt)
                wl["cl_submitted"] = wl["cl_submitted"] + cnt
        wl["buffer"] = wl["buffer"] + cnt
        wl["buffer_tsum"] = wl["buffer_tsum"] + cnt * t
        return wl


def refill_cpu(wl: Dict, cpu_req_per_tick: jax.Array) -> Dict:
    wl = dict(wl)
    wl["cpu_tokens"] = jnp.minimum(wl["cpu_tokens"] + cpu_req_per_tick, 1e7)
    return wl


def form_batches(wl: Dict, t: jax.Array, can_form: jax.Array,
                 round_idx: jax.Array, batch_size: int, batch_ticks: float
                 ) -> Tuple[Dict, jax.Array, jax.Array]:
    """can_form: [n] bool (protocol gate, e.g. ~awaitingAcks & alive).
    round_idx: [n] int32 — the chain round the new batch would get.
    Returns (wl, formed [n] bool, count [n] float)."""
    wl = dict(wl)
    size_ok = wl["buffer"] >= batch_size
    time_ok = (t - wl["last_batch_t"] >= batch_ticks) & (wl["buffer"] > 0)
    formed = can_form & (size_ok | time_ok) & (wl["cpu_tokens"] >= 1.0)
    count = jnp.where(formed,
                      jnp.minimum(jnp.minimum(wl["buffer"], batch_size),
                                  wl["cpu_tokens"]), 0.0)
    frac = jnp.where(wl["buffer"] > 0, count / jnp.maximum(wl["buffer"], 1.0), 0.0)
    tsum_taken = wl["buffer_tsum"] * frac
    arr_mean = jnp.where(count > 0, tsum_taken / jnp.maximum(count, 1.0), 0.0)
    n = count.shape[0]
    rows = jnp.arange(n)
    idx = jnp.clip(round_idx, 0, wl["batch_create_t"].shape[1] - 1)
    wl["batch_create_t"] = wl["batch_create_t"].at[rows, idx].min(
        jnp.where(formed, t.astype(jnp.float32), jnp.inf))
    wl["batch_arr_mean"] = wl["batch_arr_mean"].at[rows, idx].add(
        jnp.where(formed, arr_mean, 0.0))
    wl["batch_count"] = wl["batch_count"].at[rows, idx].add(count)
    if "batch_count_cum" in wl:
        prev = wl["batch_count_cum"][rows, jnp.maximum(idx - 1, 0)]
        wl["batch_count_cum"] = wl["batch_count_cum"].at[rows, idx].set(
            jnp.where(formed, prev + count,
                      wl["batch_count_cum"][rows, idx]))
    wl["buffer"] = wl["buffer"] - count
    wl["buffer_tsum"] = wl["buffer_tsum"] - tsum_taken
    wl["cpu_tokens"] = wl["cpu_tokens"] - count
    wl["last_batch_t"] = jnp.where(formed, t.astype(jnp.float32),
                                   wl["last_batch_t"])
    return wl, formed, count
