"""Mandator (Algorithm 1) — consensus-agnostic asynchronous request
dissemination, faithful to the paper:

- every replica runs its own chain of Mandator-batches,
- a batch is broadcast, voted, and *completed* once n-f <Mandator-vote>s
  arrive; the next batch (carrying lastCompletedRounds implicitly through
  its parent link) is only formed after completion (awaitingAcks gate),
- getClientRequests() returns the replica's lastCompletedRounds[] vector
  clock — the only thing the consensus layer ever orders.

Simulator mapping: <new-Mandator-batch> and <Mandator-vote> are monotone
payloads (round numbers), so channel merges are benign (channel.py).
Implementation §4 notes: child processes and selective-broadcast change
constants (hop count / memory), not the algorithm; we model the 1-child
configuration's bandwidth on the replica NIC directly (DESIGN.md §8).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.smr import SMRConfig
from repro.core import channel as ch
from repro.core import netsim, workload
from repro.obs import monitor as hmon
from repro.obs import trace as obs

def ring_spec() -> ch.RingSpec:
    """Packed delivery ring: both message types in one fused buffer."""
    return ch.RingSpec(
        ch.ChannelSpec("batch", 2),    # (round, lastCompleted)
        ch.ChannelSpec("vote", 1),
    )


def init_state(cfg: SMRConfig, n_ticks: int, closed: bool = False) -> Dict:
    n = cfg.n_replicas
    dmax = cfg.delay_horizon_ticks
    # flight-recorder state rides in the protocol dict; None (and absent
    # from the carry) at trace_level="off" so the untraced program is
    # structurally identical to the pre-recorder build
    tr = obs.init_trace(obs.DEFAULT_SPEC, cfg.trace_level, n,
                        cfg.trace_events)
    extra = {"tr": tr} if tr is not None else {}
    # health monitor per-tick IO gauges (repro.obs.monitor): absent at
    # monitor_level="off", same structural gating as the recorder
    if hmon.on(cfg.monitor_level):
        extra["mon_io"] = {"dropped": jnp.zeros((n,), jnp.int32)}
    return {
        **extra,
        "wl": workload.init_workload(cfg, n_ticks, closed=closed),
        "own_round": jnp.zeros((n,), jnp.int32),       # last completed round
        "formed_round": jnp.zeros((n,), jnp.int32),    # last formed round
        "lcr": jnp.zeros((n, n), jnp.int32),           # i's lastCompletedRounds
        "seen_round": jnp.zeros((n, n), jnp.int32),    # i's max batch seen from j
        "vote_max": jnp.zeros((n, n), jnp.int32),      # votes i received from j
        "ring": ch.make_ring(ring_spec(), dmax, n),
        "egress_busy": jnp.zeros((n,), jnp.float32),
    }


def tick(st: Dict, t: jax.Array, draw: jax.Array, env: Dict, cfg: SMRConfig,
         rate_per_tick: jax.Array, wlt: Dict | None = None,
         mode: workload.WorkloadMode = workload.TRIVIAL_MODE) -> Dict:
    """One simulator tick of Mandator dissemination, under the
    ``mandator`` named scope (the device trace's per-layer time).
    ``draw`` is the tick's arrivals as ``workload.arrive`` takes them."""
    with jax.named_scope("mandator"):
        return _tick(st, t, draw, env, cfg, rate_per_tick, wlt, mode)


def _tick(st: Dict, t: jax.Array, draw: jax.Array, env: Dict, cfg: SMRConfig,
          rate_per_tick: jax.Array, wlt: Dict | None,
          mode: workload.WorkloadMode) -> Dict:
    n = cfg.n_replicas
    quorum = cfg.quorum
    alive = netsim.alive(env, t)
    delays = netsim.link_delay(env, t)
    drop = netsim.link_drop(env, t)
    st = dict(st)
    # one fused pop of slot t for every channel; sends buffer up and commit
    # as one fused scatter at the end of the tick (same-tick sends always
    # land at t+1 or later, so the reorder is exact — channel.py)
    spec = ring_spec()
    msgs = ch.ring_deliver(spec, st["ring"], t)
    sends = []

    # 1) client arrivals + cpu refill
    wl = workload.arrive(st["wl"], draw, t, rate_per_tick, alive, wlt, mode)
    wl = workload.refill_cpu(wl, env["cpu_req_per_tick"])

    # 2) deliver <new-Mandator-batch>: update seen rounds + lcr, send votes
    bflags, bpayload = msgs["batch"]
    folded = ch.fold_state(
        jnp.stack([st["seen_round"], st["lcr"]], axis=-1).astype(jnp.float32),
        bflags, bpayload)
    seen = folded[..., 0].astype(jnp.int32)
    # batch carries its creator's lastCompletedRounds (parent link, line 15)
    lcr = folded[..., 1].astype(jnp.int32)
    # vote for every newly seen batch (line 16): cumulative vote = max round
    vote_mask = jnp.swapaxes(bflags, 0, 1) & alive[:, None]   # [voter, owner]
    vote_payload = seen.astype(jnp.float32)[..., None]        # [n, n, 1]
    sends.append(ch.Send("vote", vote_payload, delays.astype(jnp.int32),
                         vote_mask))

    # 3) deliver votes; in-order completion check (lines 17-19); with lanes,
    #    several rounds may complete back-to-back in one tick
    vflags, vpayload = msgs["vote"]
    vote_max = ch.fold_state(st["vote_max"].astype(jnp.float32)[..., None],
                             vflags, vpayload)[..., 0].astype(jnp.int32)
    own_round = st["own_round"]
    for _ in range(cfg.mandator_lanes):
        await_round = own_round + 1
        votes = jnp.sum(vote_max >= await_round[:, None], axis=1)
        done = (st["formed_round"] >= await_round) & (votes >= quorum)
        own_round = jnp.where(done, await_round, own_round)
    lcr = lcr.at[jnp.arange(n), jnp.arange(n)].set(own_round)

    # 4) form + broadcast next batch (lines 8-12); §4 child processes allow
    #    up to `mandator_lanes` outstanding batches per chain
    can_form = alive & (st["formed_round"] - own_round < cfg.mandator_lanes)
    wl, formed, count = workload.form_batches(
        wl, t, can_form, st["formed_round"] + 1, cfg.batch_mandator,
        cfg.max_batch_ms / cfg.tick_ms)
    formed_round = jnp.where(formed, st["formed_round"] + 1, st["formed_round"])
    # child processes serialize on their own NIC share; we model the replica
    # NIC as the shared egress (DESIGN.md §8)
    bytes_out = (count * cfg.request_bytes + 100.0)[:, None] * formed[:, None]
    bytes_out = jnp.broadcast_to(bytes_out, (n, n)) \
        / netsim.nic_rate(env, t)[:, None]
    busy, ser_delay = netsim.egress_delay(st["egress_busy"], t, bytes_out)
    busy = jnp.where(formed, busy, st["egress_busy"])
    total_delay = (delays + jnp.where(formed[:, None], ser_delay, 0.0)
                   ).astype(jnp.int32)
    bpay = jnp.stack([formed_round, own_round], axis=-1).astype(
        jnp.float32)[:, None, :] * jnp.ones((n, n, 1))
    sends.append(ch.Send("batch", bpay, total_delay,
                         formed[:, None] & jnp.ones((n, n), jnp.bool_)))

    ring = ch.ring_commit(spec, st["ring"], t, sends, drop=drop,
                          backend=cfg.channel_backend)

    # ---- flight recorder + monitor IO (absent => compiled out) ------------
    tr = st.get("tr")
    if tr is not None or "mon_io" in st:
        cut = jnp.sum(vote_mask & drop, axis=1) \
            + jnp.sum(formed[:, None] & drop, axis=1)
    if tr is not None:
        es = obs.DEFAULT_SPEC
        completed = own_round - st["own_round"]
        done = completed > 0
        tr = obs.record(es, tr, "batch_ack", done, t, a=own_round, b=quorum)
        tr = obs.record(es, tr, "batch_stable", done, t, a=own_round,
                        b=completed)
        tr = obs.record(es, tr, "batch_create", formed, t, a=formed_round,
                        b=count)
        tr = obs.record(es, tr, "batch_disseminate", formed, t,
                        a=formed_round, b=jnp.max(ser_delay, axis=1))
        tr = obs.record_env(es, tr, alive, t, a=own_round, b=formed_round,
                            dropped_links=cut)
        st["tr"] = tr
    if "mon_io" in st:
        st["mon_io"] = {"dropped": cut.astype(jnp.int32)}

    st.update(wl=wl, own_round=own_round, formed_round=formed_round, lcr=lcr,
              seen_round=seen, vote_max=vote_max, ring=ring,
              egress_busy=busy)
    return st


def get_client_requests(st: Dict) -> jax.Array:
    """lastCompletedRounds — the consensus payload (line 20-21). [n, n]."""
    return st["lcr"]
