"""Client arrivals (open- or closed-loop) + batch bookkeeping.

Arrivals are Poisson per tick per origin, keyed by
``fold_in(PRNGKey(seed), t)``. The mean comes from one of two
statically-selected paths (``repro.workloads.WorkloadMode``):

  trivial — the seed-era §5.2 baseline: ``rate_per_tick`` broadcast to all
            origins (what keeps the fig 6-9 artifacts byte-identical);
  table   — ``rate_per_tick x rate_of[win_of_tick[t]]`` from a compiled
            ``repro.workloads`` rate table; in closed mode the table
            instead sizes geo-placed client pools (Little's law) whose
            submission rate is gated on in-flight requests and capped at
            ``cap`` outstanding per origin.

In the open modes the mean depends on nothing in the scan carry, so a
point's whole ``[n_ticks, n]`` table of draws is made once, before the
tick scan (``draw_arrivals``), and each tick reads its row. In closed mode
the mean depends on the requests in flight, so each tick draws its own
counts inside the scan. Both paths call the same sampler on the same keys
and means, so the draws are bitwise equal.

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


def _open_mean(rate_per_tick: jax.Array, t: jax.Array, n: int,
               wlt: Optional[Dict], mode: WorkloadMode) -> jax.Array:
    """The open-loop Poisson mean at tick ``t`` ([n]) or at each tick of a
    vector ``t`` ([len(t), n]). The one expression both arrival paths use,
    so the hoisted and the in-scan draws cannot drift apart."""
    if mode.trivial:
        return jnp.broadcast_to(rate_per_tick, jnp.shape(t) + (n,))
    return rate_per_tick * wlt["rate_of"][wlt["win_of_tick"][t]]


def draw_arrivals(seed_key: jax.Array, n_ticks: int, rate_per_tick: jax.Array,
                  n: int, wlt: Optional[Dict] = None,
                  mode: WorkloadMode = TRIVIAL_MODE) -> jax.Array:
    """A point's raw open-loop Poisson counts, ``[n_ticks, n]`` float32:
    row t is ``poisson(fold_in(seed_key, t), mean_t)``, bitwise what a
    per-tick draw inside the scan gives. One sampler call for the whole
    point, under the ``arrivals`` named scope. Open modes only: a
    closed-loop mean depends on the scan carry."""
    with jax.named_scope("arrivals"):
        ticks = jnp.arange(n_ticks, dtype=jnp.int32)
        keys = jax.vmap(lambda t: jax.random.fold_in(seed_key, t))(ticks)
        lam = _open_mean(rate_per_tick, ticks, n, wlt, mode)
        return jax.vmap(jax.random.poisson)(keys, lam).astype(jnp.float32)


def arrive(wl: Dict, draw: jax.Array, t: jax.Array, rate_per_tick: jax.Array,
           alive: jax.Array, wlt: Optional[Dict] = None,
           mode: WorkloadMode = TRIVIAL_MODE) -> Dict:
    """This tick's arrivals at each origin's clients. In the open modes
    ``draw`` is the tick's row of ``draw_arrivals``; in closed mode it is
    the tick's PRNG key, and the counts are drawn here from the gated
    mean. ``wlt`` is the compiled workload table (required unless
    mode.trivial). Runs under the ``arrivals`` named scope, the layer the
    device trace reads."""
    with jax.named_scope("arrivals"):
        wl = dict(wl)
        if not mode.closed:
            cnt = draw * alive
        else:
            # pool size via Little's law at the sweep rate; submission is
            # gated on requests still in flight and capped at `cap`
            mult = wlt["rate_of"][wlt["win_of_tick"][t]]           # [n]
            lam = _open_mean(rate_per_tick, t, alive.shape[0], wlt, mode)
            inflight = wl["cl_submitted"] - wl["cl_done"]
            clients = rate_per_tick * wlt["think_ticks"] * mult
            lam_cl = jnp.clip(clients - inflight, 0.0) / wlt["think_ticks"]
            lam = jnp.where(wlt["closed"] > 0, lam_cl, lam)
            cnt = jax.random.poisson(draw, lam).astype(jnp.float32) * alive
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
