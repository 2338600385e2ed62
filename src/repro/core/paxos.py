"""Multi-Paxos baseline (§5's monolithic leader-based protocol) and its
Mandator composition (Mandator-Paxos).

Plain mode: clients forward requests to the current leader; the leader runs
one consensus slot at a time (no pipelining, §5.2) carrying the request
batch *in* the accept message (the monolithic anti-pattern the paper
targets) — throughput is bound by batch/slot-RTT and the leader's NIC.

Mandator mode: the slot payload is the leader's lastCompletedRounds vector
clock (meta_bytes), committing every disseminated batch it dominates.

View change: follower timeout -> view++ (rotating leader); a new leader
runs phase-1 (modeled as one majority-RTT delay) before proposing. Requests
forwarded to a failed leader are lost to the count (client-retry is not
modeled; noted in DESIGN.md §8) — the crash-dip in fig7 is the phenomenon
under study.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.smr import SMRConfig
from repro.core import channel as ch
from repro.core import netsim, workload
from repro.obs import monitor as hmon
from repro.obs import trace as obs

def _phase1_ticks(cfg: SMRConfig) -> jnp.ndarray:
    """Majority RTT per prospective leader (modeled phase-1 cost)."""
    d = cfg.delays_ms() / cfg.tick_ms
    n = cfg.n_replicas
    maj = n // 2 + 1
    rtts = np.sort(2 * d, axis=1)[:, maj - 1]
    return jnp.asarray(rtts, jnp.float32)


def ring_spec(n: int, mandator_mode: bool) -> ch.RingSpec:
    """Packed delivery ring. The additive request-forward channel only
    exists in plain mode (mandator mode orders vector clocks, clients
    never forward), so its fields drop out of the ring entirely there."""
    channels = () if mandator_mode else (
        ch.ChannelSpec("fw", 2, additive=True),)      # (count, tsum)
    return ch.RingSpec(
        *channels,
        ch.ChannelSpec("acc", 3 + n),                 # (view, slot, ., vc)
        ch.ChannelSpec("ack", 1),
    )


def init_state(cfg: SMRConfig, n_ticks: int, mandator_mode: bool,
               closed: bool = False) -> Dict:
    n = cfg.n_replicas
    dmax = cfg.delay_horizon_ticks
    # flight recorder: absent at trace_level="off" (see mandator.init_state)
    tr = obs.init_trace(obs.DEFAULT_SPEC, cfg.trace_level, n,
                        cfg.trace_events)
    extra = {"tr": tr} if tr is not None else {}
    # health monitor per-tick IO gauges: absent at monitor_level="off"
    if hmon.on(cfg.monitor_level):
        extra["mon_io"] = {"dropped": jnp.zeros((n,), jnp.int32)}
    return {
        **extra,
        "wl": workload.init_workload(cfg, n_ticks,
                                     closed=closed and not mandator_mode),
        "view": jnp.zeros((n,), jnp.int32),
        "last_heard": jnp.zeros((n,), jnp.float32),
        "ready_at": jnp.zeros((n,), jnp.float32),
        "slot": jnp.zeros((n,), jnp.int32),           # leader's last started
        "outstanding": jnp.zeros((n,), jnp.bool_),
        "acks": jnp.zeros((n, n), jnp.int32),         # max slot acked by j
        "committed_slot": jnp.zeros((n,), jnp.int32),
        "cvc": jnp.zeros((n, n), jnp.int32),          # mandator mode commit VC
        "slot_vc": jnp.zeros((n, 1 + n), jnp.float32),  # outstanding slot payload
        "ring": ch.make_ring(ring_spec(n, mandator_mode), dmax, n),
        "egress_busy": jnp.zeros((n,), jnp.float32),
        "phase1": _phase1_ticks(cfg),
    }


def tick(st: Dict, t: jax.Array, draw: jax.Array | None, env: Dict,
         cfg: SMRConfig, rate_per_tick: jax.Array, mandator_mode: bool,
         lcr: jax.Array | None = None, wlt: Dict | None = None,
         mode: workload.WorkloadMode = workload.TRIVIAL_MODE) -> Dict:
    """One simulator tick of (Mandator-)Paxos, under the ``paxos`` named
    scope (the device trace's per-layer time). ``draw`` is the tick's
    arrivals as ``workload.arrive`` takes them; None in mandator mode,
    where Mandator takes the arrivals."""
    with jax.named_scope("paxos"):
        return _tick(st, t, draw, env, cfg, rate_per_tick, mandator_mode,
                     lcr, wlt, mode)


def _tick(st: Dict, t: jax.Array, draw: jax.Array | None, env: Dict,
          cfg: SMRConfig, rate_per_tick: jax.Array, mandator_mode: bool,
          lcr: jax.Array | None, wlt: Dict | None,
          mode: workload.WorkloadMode) -> Dict:
    n = cfg.n_replicas
    maj = n // 2 + 1
    alive = netsim.alive(env, t)
    delays = netsim.link_delay(env, t).astype(jnp.int32)
    drop = netsim.link_drop(env, t)
    to_ticks = jnp.float32(cfg.view_timeout_ms / cfg.tick_ms)
    tf = t.astype(jnp.float32)
    st = dict(st)
    rows = jnp.arange(n)

    view = st["view"]
    leader = view % n
    i_am_leader = (leader == rows) & alive
    # one fused pop of slot t for every channel; sends buffer up and commit
    # as one fused scatter at the end of the tick (same-tick sends always
    # land at t+1 or later, so the reorder is exact — channel.py)
    spec = ring_spec(n, mandator_mode)
    msgs = ch.ring_deliver(spec, st["ring"], t)
    sends = []

    wl = workload.refill_cpu(st["wl"], env["cpu_req_per_tick"])

    # ---- request forwarding (plain mode) ----------------------------------
    if not mandator_mode:
        wl = workload.arrive(wl, draw, t, rate_per_tick, alive, wlt, mode)
        # forward whole local buffer to my current leader
        cnt = wl["buffer"]
        tsum = wl["buffer_tsum"]
        fw_pay = jnp.stack([cnt, tsum], axis=-1)[:, None, :] * jnp.ones((n, n, 1))
        # the leader keeps local arrivals in its own pool (no self-forward)
        fw_mask = (jnp.arange(n)[None, :] == leader[:, None]) & alive[:, None] \
            & (cnt > 0)[:, None] & (rows != leader)[:, None]
        sends.append(ch.Send("fw", fw_pay, delays, fw_mask))
        wl = dict(wl)
        # the forward channel is additive (counters), so a scenario-dropped
        # link is NOT a tolerable omission: keep the batch buffered and
        # retry next tick instead of destroying the requests
        sent = (fw_mask & ~drop).any(axis=1)
        wl["buffer"] = jnp.where(sent, 0.0, wl["buffer"])
        wl["buffer_tsum"] = jnp.where(sent, 0.0, wl["buffer_tsum"])
        # leader pools forwarded requests
        ffl, fpay = msgs["fw"]
        pool_cnt = jnp.sum(jnp.where(ffl[..., None], fpay, 0.0), axis=0)  # [rcv,2]
        wl["buffer"] = wl["buffer"] + pool_cnt[:, 0]
        wl["buffer_tsum"] = wl["buffer_tsum"] + pool_cnt[:, 1]

    # ---- deliver acks; leader commit ---------------------------------------
    afl, apay = msgs["ack"]
    acks = ch.fold_state(st["acks"].astype(jnp.float32)[..., None], afl, apay
                         )[..., 0].astype(jnp.int32)
    ack_cnt = jnp.sum(acks >= st["slot"][:, None], axis=1)
    commit = i_am_leader & st["outstanding"] & (ack_cnt >= maj)
    committed_slot = jnp.where(commit, st["slot"], st["committed_slot"])
    outstanding = st["outstanding"] & ~commit
    # record commit time of the slot batch (plain) / advance VC (mandator)
    if mandator_mode:
        cvc = jnp.where(commit[:, None],
                        jnp.maximum(st["cvc"], st["slot_vc"][:, 1:].astype(jnp.int32)),
                        st["cvc"])
    else:
        cvc = st["cvc"]
        # commit times are recorded post-hoc from the committed_slot trace
    # ---- leader proposes next slot -----------------------------------------
    can_prop = i_am_leader & ~outstanding & (tf >= st["ready_at"])
    if mandator_mode:
        have = (lcr[rows] > cvc).any(axis=1) if lcr is not None else False
        have = have & can_prop
        slot = jnp.where(have, st["slot"] + 1, st["slot"])
        pay_vc = jnp.where(have[:, None], lcr[rows].astype(jnp.float32),
                           st["slot_vc"][:, 1:])
        slot_vc = jnp.concatenate(
            [slot[:, None].astype(jnp.float32), pay_vc], axis=1)
        size_bytes = jnp.where(have, jnp.float32(cfg.meta_bytes), 0.0)
        formed = have
        count = jnp.zeros((n,))
    else:
        wl, formed, count = workload.form_batches(
            wl, t, can_prop, st["slot"] + 1, cfg.batch_paxos,
            cfg.max_batch_ms / cfg.tick_ms)
        slot = jnp.where(formed, st["slot"] + 1, st["slot"])
        slot_vc = st["slot_vc"]
        size_bytes = jnp.where(formed, count * cfg.request_bytes + 100.0, 0.0)
    outstanding = outstanding | formed
    # egress serialization (monolithic payload cost)
    bytes_out = jnp.broadcast_to(size_bytes[:, None], (n, n)) \
        / netsim.nic_rate(env, t)[:, None]
    busy, ser = netsim.egress_delay(st["egress_busy"], t, bytes_out)
    busy = jnp.where(formed, busy, st["egress_busy"])
    total_delay = (delays + jnp.where(formed[:, None], ser, 0.0)).astype(jnp.int32)
    acc_pay = jnp.concatenate([
        view[:, None].astype(jnp.float32), slot[:, None].astype(jnp.float32),
        jnp.zeros((n, 1)),
        slot_vc[:, 1:] if mandator_mode else jnp.zeros((n, n))], axis=1
        )[:, None, :] * jnp.ones((n, n, 1))
    sends.append(ch.Send("acc", acc_pay, total_delay,
                         formed[:, None] & jnp.ones((n, n), jnp.bool_)))

    # ---- follower: deliver accepts, ack, heartbeat --------------------------
    cfl, cpay = msgs["acc"]
    arr = jnp.swapaxes(cpay, 0, 1)
    afl2 = jnp.swapaxes(cfl, 0, 1)
    got = afl2.any(axis=1)
    mx = jnp.max(jnp.where(afl2[..., None], arr, -1.0), axis=1)
    acc_view = mx[:, 0].astype(jnp.int32)
    acc_slot = mx[:, 1].astype(jnp.int32)
    fresh = got & (acc_view >= view) & alive
    view = jnp.where(fresh, acc_view, view)
    last_heard = jnp.where(fresh, tf, st["last_heard"])
    # ack to the slot's leader
    ack_mask = fresh[:, None] & (jnp.arange(n)[None, :] == (view % n)[:, None])
    ack_pay = acc_slot.astype(jnp.float32)[:, None, None] * jnp.ones((n, n, 1))
    sends.append(ch.Send("ack", ack_pay, delays, ack_mask))

    # ---- view change ---------------------------------------------------------
    expired = alive & (tf - last_heard > to_ticks)
    view = jnp.where(expired, view + 1, view)
    last_heard = jnp.where(expired, tf, last_heard)
    became_leader = expired & ((view % n) == rows)
    ready_at = jnp.where(became_leader, tf + st["phase1"], st["ready_at"])

    ring = ch.ring_commit(spec, st["ring"], t, sends, drop=drop,
                          backend=cfg.channel_backend)

    # ---- flight recorder + monitor IO (absent => compiled out) ------------
    tr = st.get("tr")
    if tr is not None or "mon_io" in st:
        sent_any = sends[0].mask
        for s in sends[1:]:
            sent_any = sent_any | s.mask
        cut = jnp.sum(sent_any & drop, axis=1)
    if tr is not None:
        es = obs.DEFAULT_SPEC
        tr = obs.record(es, tr, "view_change", view != st["view"], t,
                        a=view, b=slot)
        tr = obs.record(es, tr, "leader_change", became_leader, t,
                        a=view % n, b=view)
        tr = obs.record(es, tr, "commit", commit, t, a=committed_slot,
                        b=ack_cnt)
        tr = obs.record(es, tr, "batch_create", formed, t, a=slot, b=count)
        tr = obs.record(es, tr, "batch_disseminate", formed, t, a=slot,
                        b=jnp.max(ser, axis=1))
        tr = obs.record_env(es, tr, alive, t, a=view, b=slot,
                            dropped_links=cut)
        st["tr"] = tr
    if "mon_io" in st:
        st["mon_io"] = {"dropped": cut.astype(jnp.int32)}

    st.update(wl=wl, view=view, last_heard=last_heard, ready_at=ready_at,
              slot=slot, outstanding=outstanding, acks=acks,
              committed_slot=committed_slot, cvc=cvc, slot_vc=slot_vc,
              ring=ring, egress_busy=busy)
    return st
