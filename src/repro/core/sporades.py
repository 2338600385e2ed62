"""Sporades (Algorithms 2 + 3) — dual-mode omission-fault-tolerant consensus,
composed with Mandator: block payloads are Mandator vector clocks.

Faithful protocol, simulator-native encoding:
- rank (v, r) is packed into an int key  v*RS + r  (lexicographic order
  preserved; RS bounds rounds-per-view); float32 channel payloads stay
  exact below 2^24.
- every message type is a monotone payload (see channel.py); receivers keep
  *latest-state* matrices and triggers fire on state predicates, not message
  events — so a replica that exits the async path still reacts to votes that
  arrived while it was async (omission-tolerant by construction).
- the common coin is the shared-seed PRNG of core/coin.py (§3.2.1).

Synchronous path: lines 9-28 of Alg. 2. Asynchronous path: lines 1-36 of
Alg. 3, including Bfall catch-up and the "first n-f asynchronous-complete"
commit rule (tracked via arrival ticks).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.smr import SMRConfig
from repro.core import channel as ch
from repro.core import netsim
from repro.core.coin import coin_table
from repro.obs import monitor as hmon
from repro.obs import trace as obs

RS = 1 << 14                    # rounds-per-view bound (rank key packing)
MAX_VIEWS = 4096


def key(v, r):
    return v * RS + r


def ring_spec(n: int) -> ch.RingSpec:
    """Packed delivery ring: all six Sporades message types in one fused
    [Dmax, n, n, K] buffer (the seed carried six separate rings)."""
    return ch.RingSpec(
        ch.ChannelSpec("prop", 2 + 2 * n),
        ch.ChannelSpec("vote", 2 + n),
        ch.ChannelSpec("to", 2 + n),
        ch.ChannelSpec("pa", 1 + n),
        ch.ChannelSpec("va", n),
        ch.ChannelSpec("ac", 2 + n),
    )


def init_state(cfg: SMRConfig, n_ticks: int) -> Dict:
    n = cfg.n_replicas
    dmax = cfg.delay_horizon_ticks
    z = lambda *s: jnp.zeros(s, jnp.int32)
    # flight recorder: absent at trace_level="off" (see mandator.init_state)
    tr = obs.init_trace(obs.DEFAULT_SPEC, cfg.trace_level, n,
                        cfg.trace_events)
    extra = {"tr": tr} if tr is not None else {}
    # health monitor per-tick IO gauges: absent at monitor_level="off"
    if hmon.on(cfg.monitor_level):
        extra["mon_io"] = {"dropped": jnp.zeros((n,), jnp.int32)}
    return {
        **extra,
        "v_cur": z(n), "r_cur": z(n),
        "is_async": jnp.zeros((n,), jnp.bool_),
        "bh_key": z(n), "bh_vc": z(n, n),
        "commit_key": z(n), "cvc": z(n, n),
        "prop_key": z(n), "last_vote_trig": jnp.full((n,), -1, jnp.int32),
        # first deadline = one view timeout from t=0
        "deadline": jnp.full((n,), cfg.view_timeout_ms / cfg.tick_ms,
                             jnp.float32),
        "timeout_sent_v": jnp.full((n,), -1, jnp.int32),
        "async_phase": z(n), "my_r": z(n), "my_avc": z(n, n),
        "exited_view": jnp.full((n,), -1, jnp.int32),
        "ac_tick": jnp.full((n, n), jnp.inf, jnp.float32),
        "ac_v_seen": jnp.full((n, n), -1, jnp.int32),
        # latest-state matrices [receiver, sender, fields]
        "vote_st": jnp.zeros((n, n, 2 + n), jnp.float32),
        "to_st": jnp.full((n, n, 2 + n), -1.0, jnp.float32),
        "pa_st": jnp.full((n, n, 1 + n), -1.0, jnp.float32),
        # vote-async is broadcast; field p of a voter's payload is the key of
        # the latest block from proposer p it voted for (enables the
        # Theorem-9 catch-up: adopt any h1 that gathered n-f votes)
        "va_st": jnp.full((n, n, n), -1.0, jnp.float32),
        "ac_st": jnp.full((n, n, 2 + n), -1.0, jnp.float32),
        # all six message types share ONE packed delivery ring
        "ring": ch.make_ring(ring_spec(n), dmax, n),
        "coins": coin_table(MAX_VIEWS, n),
    }


def _leader_of(v, n):
    return v % n


def tick(st: Dict, t: jax.Array, env: Dict, cfg: SMRConfig,
         lcr: jax.Array) -> Dict:
    """One simulator tick, under the ``sporades`` named scope (the device
    trace's per-layer time). lcr: Mandator getClientRequests() per replica
    [n, n] (row i = replica i's vector clock)."""
    with jax.named_scope("sporades"):
        return _tick(st, t, env, cfg, lcr)


def _tick(st: Dict, t: jax.Array, env: Dict, cfg: SMRConfig,
          lcr: jax.Array) -> Dict:
    n = cfg.n_replicas
    q = cfg.quorum
    alive = netsim.alive(env, t)
    delays = netsim.link_delay(env, t).astype(jnp.int32)
    drop = netsim.link_drop(env, t)
    to_ticks = jnp.float32(cfg.view_timeout_ms / cfg.tick_ms)
    st = dict(st)
    tf = t.astype(jnp.float32)
    rows = jnp.arange(n)
    lcr_f = lcr.astype(jnp.float32)
    # one fused pop of slot t for every channel; sends buffer up and commit
    # as one fused scatter at the end of the tick (same-tick sends always
    # land at t+1 or later, so the reorder is exact — channel.py)
    spec = ring_spec(n)
    msgs = ch.ring_deliver(spec, st["ring"], t)
    sends = []

    v_cur, r_cur = st["v_cur"], st["r_cur"]
    is_async = st["is_async"]
    bh_key, bh_vc = st["bh_key"], st["bh_vc"].astype(jnp.float32)
    commit_key, cvc = st["commit_key"], st["cvc"].astype(jnp.float32)
    deadline = st["deadline"]

    # ---- 1) deliver <propose> (Alg2 lines 20-26) --------------------------
    pfl, ppay = msgs["prop"]
    arr = jnp.swapaxes(ppay, 0, 1)                       # [rcv, snd, P]
    afl = jnp.swapaxes(pfl, 0, 1)
    ps = jnp.max(jnp.where(afl[..., None], arr, -1.0), axis=1)   # [rcv, P]
    got_prop = afl.any(axis=1)
    pb_key = ps[:, 0].astype(jnp.int32)
    pc_key = ps[:, 1].astype(jnp.int32)
    p_vc = ps[:, 2:2 + n]
    p_cvc = ps[:, 2 + n:]
    accept = got_prop & alive & ~is_async & (pb_key > key(v_cur, r_cur))
    cvc = jnp.where(accept[:, None], jnp.maximum(cvc, p_cvc), cvc)
    commit_key = jnp.where(accept, jnp.maximum(commit_key, pc_key), commit_key)
    v_cur = jnp.where(accept, pb_key // RS, v_cur)
    r_cur = jnp.where(accept, pb_key % RS, r_cur)
    bh_key = jnp.where(accept, pb_key, bh_key)
    bh_vc = jnp.where(accept[:, None], p_vc, bh_vc)
    deadline = jnp.where(accept, tf + to_ticks, deadline)
    # send <vote> to L_v (line 25)
    vote_pay = jnp.concatenate(
        [bh_key[:, None].astype(jnp.float32), bh_key[:, None].astype(jnp.float32),
         bh_vc], axis=1)[:, None, :] * jnp.ones((n, n, 1))
    vote_mask = accept[:, None] & (jnp.arange(n)[None, :]
                                   == _leader_of(v_cur, n)[:, None])
    sends.append(ch.Send("vote", vote_pay, delays, vote_mask))

    # ---- 2) deliver <vote>; leader trigger (Alg2 lines 9-19) --------------
    vfl, vpay = msgs["vote"]
    vote_st = ch.fold_state(st["vote_st"], vfl, vpay)
    voted = vote_st[:, :, 0].astype(jnp.int32)           # [ldr, voter]
    kmax = jnp.max(voted, axis=1)
    match = voted == kmax[:, None]
    cnt = jnp.sum(match, axis=1)
    lead_trig = (alive & ~is_async & (cnt >= q)
                 & (kmax >= key(v_cur, r_cur)) & (kmax > st["last_vote_trig"])
                 & (_leader_of(kmax // RS, n) == rows))
    vbh = vote_st[:, :, 1].astype(jnp.int32)
    bh_new = jnp.max(jnp.where(match, vbh, -1), axis=1)
    vvc = vote_st[:, :, 2:]
    bh_vc_new = jnp.max(jnp.where(match[..., None], vvc, -1.0), axis=1)
    # commit check (line 11): n-f votes whose block_high rank == voted rank
    cnt_bh = jnp.sum(match & (vbh == kmax[:, None]), axis=1)
    lead_commit = lead_trig & (cnt_bh >= q)
    commit_key = jnp.where(lead_commit, jnp.maximum(commit_key, kmax), commit_key)
    cvc = jnp.where(lead_commit[:, None], jnp.maximum(cvc, bh_vc_new), cvc)
    v_cur = jnp.where(lead_trig, kmax // RS, v_cur)
    r_cur = jnp.where(lead_trig, kmax % RS, r_cur)
    bh_key = jnp.where(lead_trig, jnp.maximum(bh_key, bh_new), bh_key)
    bh_vc = jnp.where(lead_trig[:, None], jnp.maximum(bh_vc, bh_vc_new), bh_vc)
    # form + broadcast new block (lines 15-18)
    new_key = key(v_cur, r_cur + 1)
    prop_vc = jnp.maximum(lcr_f, bh_vc)
    prop_pay = jnp.concatenate(
        [new_key[:, None].astype(jnp.float32),
         commit_key[:, None].astype(jnp.float32), prop_vc, cvc],
        axis=1)[:, None, :] * jnp.ones((n, n, 1))
    sends.append(ch.Send("prop", prop_pay, delays,
                         lead_trig[:, None] & jnp.ones((n, n), jnp.bool_)))
    prop_key = jnp.where(lead_trig, new_key, st["prop_key"])
    # (leader's own block_high advances via self-delivery of its propose)
    last_vote_trig = jnp.where(lead_trig, kmax, st["last_vote_trig"])

    # ---- 3) timeout (Alg2 lines 27-28) ------------------------------------
    fire = alive & ~is_async & (tf >= deadline) & (st["timeout_sent_v"] < v_cur)
    to_pay = jnp.concatenate(
        [v_cur[:, None].astype(jnp.float32), bh_key[:, None].astype(jnp.float32),
         bh_vc], axis=1)[:, None, :] * jnp.ones((n, n, 1))
    sends.append(ch.Send("to", to_pay, delays,
                         fire[:, None] & jnp.ones((n, n), jnp.bool_)))
    timeout_sent_v = jnp.where(fire, v_cur, st["timeout_sent_v"])

    # ---- 4) deliver <timeout>; async entry (Alg3 lines 1-7) ---------------
    tfl, tpay = msgs["to"]
    to_st = ch.fold_state(st["to_st"], tfl, tpay)
    to_v = to_st[:, :, 0].astype(jnp.int32)
    tvmax = jnp.max(to_v, axis=1)
    tmatch = to_v == tvmax[:, None]
    tcnt = jnp.sum(tmatch, axis=1)
    enter = alive & ~is_async & (tcnt >= q) & (tvmax >= v_cur)
    tbh = jnp.max(jnp.where(tmatch, to_st[:, :, 1].astype(jnp.int32), -1), axis=1)
    tbh_vc = jnp.max(jnp.where(tmatch[..., None], to_st[:, :, 2:], -1.0), axis=1)
    bh_key = jnp.where(enter, jnp.maximum(bh_key, tbh), bh_key)
    bh_vc = jnp.where(enter[:, None], jnp.maximum(bh_vc, tbh_vc), bh_vc)
    v_cur = jnp.where(enter, tvmax, v_cur)
    r_cur = jnp.where(enter, jnp.maximum(r_cur, bh_key % RS), r_cur)
    is_async = is_async | enter
    # height-1 async block (lines 5-7)
    r1 = r_cur + 1
    avc = jnp.maximum(lcr_f, bh_vc)
    pa_key1 = (v_cur * 2 + 1) * RS + r1
    pa_pay = jnp.concatenate(
        [pa_key1[:, None].astype(jnp.float32), avc], axis=1)[:, None, :] \
        * jnp.ones((n, n, 1))
    sends.append(ch.Send("pa", pa_pay, delays,
                         enter[:, None] & jnp.ones((n, n), jnp.bool_)))
    async_phase = jnp.where(enter, 1, st["async_phase"])
    my_r = jnp.where(enter, r1, st["my_r"])
    my_avc = jnp.where(enter[:, None], avc, st["my_avc"].astype(jnp.float32))
    deadline = jnp.where(enter, jnp.inf, deadline)

    # ---- 5) deliver <propose-async>; vote (Alg3 lines 8-14) ---------------
    pafl, papay = msgs["pa"]
    pa_st = ch.fold_state(st["pa_st"], pafl, papay)
    pa_arr = jnp.swapaxes(pafl, 0, 1)                    # [rcv, snd]
    pa_k = pa_st[:, :, 0].astype(jnp.int32)
    pa_vh = pa_k // RS
    pa_h = jnp.where(pa_vh % 2 == 1, 1, 2)
    pa_v = (pa_vh - pa_h) // 2
    pa_r = pa_k % RS
    va_vote = (pa_arr & alive[:, None] & is_async[:, None]
               & (pa_v == v_cur[:, None]) & (pa_r > r_cur[:, None]))
    # broadcast vote: field p = key of p's block being voted (else -1)
    va_fields = jnp.where(va_vote, pa_k.astype(jnp.float32), -1.0)  # [i, p]
    va_pay = jnp.broadcast_to(va_fields[:, None, :], (n, n, n))
    sends.append(ch.Send(
        "va", va_pay, delays,
        va_vote.any(axis=1)[:, None] & jnp.ones((n, n), jnp.bool_)))

    # ---- 6) deliver <vote-async>; heights (Alg3 lines 15-23) --------------
    vafl, vapay = msgs["va"]
    va_st = ch.fold_state(st["va_st"], vafl, vapay)
    va_own = va_st[rows, :, rows].astype(jnp.int32)      # [rcv, voter]
    my_h1_key = (v_cur * 2 + 1) * RS + my_r
    my_h2_key = (v_cur * 2 + 2) * RS + my_r
    cnt_h1 = jnp.sum(va_own == my_h1_key[:, None], axis=1)
    cnt_h2 = jnp.sum(va_own == my_h2_key[:, None], axis=1)
    to_h2 = alive & is_async & (async_phase == 1) & (cnt_h1 >= q)
    # Theorem-9 catch-up: adopt any height-1 block of this view that
    # gathered n-f votes, if our own h1 is not getting votes
    va_all = va_st.astype(jnp.int32)                     # [rcv, voter, p]
    k_p = jnp.max(va_all, axis=1)                        # [rcv, p]
    cnt_p = jnp.sum(va_all == k_p[:, None, :], axis=1)   # [rcv, p]
    kp_vh = k_p // RS
    kp_is_h1 = (kp_vh % 2 == 1) & ((kp_vh - 1) // 2 == v_cur[:, None])
    adoptable = (cnt_p >= q) & kp_is_h1 & (k_p % RS >= my_r[:, None])
    adopt_key = jnp.max(jnp.where(adoptable, k_p, -1), axis=1)
    adopt_p = jnp.argmax(jnp.where(adoptable, k_p, -1), axis=1)
    adopt = alive & is_async & (async_phase == 1) & ~to_h2 & (adopt_key >= 0)
    # vc for the adopted parent, if we have its propose-async
    pa_p_key = jnp.take_along_axis(pa_k, adopt_p[:, None], axis=1)[:, 0]
    pa_p_vc = jnp.take_along_axis(pa_st[:, :, 1:], adopt_p[:, None, None],
                                  axis=1)[:, 0]
    adopt_vc = jnp.where((pa_p_key == adopt_key)[:, None], pa_p_vc, my_avc)
    go_h2 = to_h2 | adopt
    r2 = jnp.where(adopt, adopt_key % RS + 1, my_r + 1)
    avc2 = jnp.maximum(lcr_f, jnp.where(adopt[:, None], adopt_vc, my_avc))
    pa_key2 = (v_cur * 2 + 2) * RS + r2
    pa_pay2 = jnp.concatenate(
        [pa_key2[:, None].astype(jnp.float32), avc2], axis=1)[:, None, :] \
        * jnp.ones((n, n, 1))
    sends.append(ch.Send("pa", pa_pay2, delays,
                         go_h2[:, None] & jnp.ones((n, n), jnp.bool_)))
    my_r = jnp.where(go_h2, r2, my_r)
    my_avc = jnp.where(go_h2[:, None], avc2, my_avc)
    async_phase = jnp.where(go_h2, 2, async_phase)
    to_ac = alive & is_async & (async_phase == 2) & (cnt_h2 >= q)
    ac_pay = jnp.concatenate(
        [v_cur[:, None].astype(jnp.float32), my_r[:, None].astype(jnp.float32),
         my_avc], axis=1)[:, None, :] * jnp.ones((n, n, 1))
    sends.append(ch.Send("ac", ac_pay, delays,
                         to_ac[:, None] & jnp.ones((n, n), jnp.bool_)))
    async_phase = jnp.where(to_ac, 3, async_phase)

    # ---- 7) deliver <asynchronous-complete>; exit (Alg3 lines 24-36) ------
    acfl, acpay = msgs["ac"]
    ac_st = ch.fold_state(st["ac_st"], acfl, acpay)
    ac_arr = jnp.swapaxes(acfl, 0, 1)
    ac_v = ac_st[:, :, 0].astype(jnp.int32)
    newer = ac_arr & (ac_v > st["ac_v_seen"])
    ac_tick = jnp.where(newer, tf, st["ac_tick"])
    ac_v_seen = jnp.where(newer, ac_v, st["ac_v_seen"])
    acm = ac_v == v_cur[:, None]                          # matching this view
    ac_cnt = jnp.sum(acm, axis=1)
    exit_ = alive & is_async & (ac_cnt >= q) & (st["exited_view"] < v_cur)
    leader = st["coins"][jnp.clip(v_cur, 0, MAX_VIEWS - 1)]
    # first n-f rule: leader's ac among the q earliest arrival ticks
    tick_m = jnp.where(acm, ac_tick, jnp.inf)
    thr = jnp.sort(tick_m, axis=1)[:, q - 1]
    ldr_tick = jnp.take_along_axis(tick_m, leader[:, None], axis=1)[:, 0]
    ldr_in = jnp.take_along_axis(acm, leader[:, None], axis=1)[:, 0] \
        & (ldr_tick <= thr)
    ac_r = ac_st[:, :, 1].astype(jnp.int32)
    ldr_r = jnp.take_along_axis(ac_r, leader[:, None], axis=1)[:, 0]
    ldr_vc = jnp.take_along_axis(ac_st[:, :, 2:], leader[:, None, None], axis=1)[:, 0]
    do_commit = exit_ & ldr_in
    commit_key = jnp.where(do_commit,
                           jnp.maximum(commit_key, key(v_cur, ldr_r)), commit_key)
    cvc = jnp.where(do_commit[:, None], jnp.maximum(cvc, ldr_vc), cvc)
    bh_key = jnp.where(do_commit, key(v_cur, ldr_r), bh_key)
    bh_vc = jnp.where(do_commit[:, None], ldr_vc, bh_vc)
    # Bfall catch-up (lines 29-31): leader's height-2 seen via propose-async
    ldr_pa_v = jnp.take_along_axis(pa_v, leader[:, None], axis=1)[:, 0]
    ldr_pa_h = jnp.take_along_axis(pa_h, leader[:, None], axis=1)[:, 0]
    ldr_pa_r = jnp.take_along_axis(pa_r, leader[:, None], axis=1)[:, 0]
    ldr_pa_vc = jnp.take_along_axis(pa_st[:, :, 1:], leader[:, None, None], axis=1)[:, 0]
    bfall = exit_ & ~ldr_in & (ldr_pa_v == v_cur) & (ldr_pa_h == 2)
    bh_key = jnp.where(bfall, key(v_cur, ldr_pa_r), bh_key)
    bh_vc = jnp.where(bfall[:, None], ldr_pa_vc, bh_vc)
    exited_view = jnp.where(exit_, v_cur, st["exited_view"])
    r_cur = jnp.where(exit_, bh_key % RS, r_cur)
    v_cur = jnp.where(exit_, v_cur + 1, v_cur)
    is_async = is_async & ~exit_
    async_phase = jnp.where(exit_, 0, async_phase)
    deadline = jnp.where(exit_, tf + to_ticks, deadline)
    # vote to the next view's leader (line 35)
    ex_vote_pay = jnp.concatenate(
        [key(v_cur, r_cur)[:, None].astype(jnp.float32),
         bh_key[:, None].astype(jnp.float32), bh_vc], axis=1)[:, None, :] \
        * jnp.ones((n, n, 1))
    ex_vote_mask = exit_[:, None] & (jnp.arange(n)[None, :]
                                     == _leader_of(v_cur, n)[:, None])
    sends.append(ch.Send("vote", ex_vote_pay, delays, ex_vote_mask))

    ring = ch.ring_commit(spec, st["ring"], t, sends, drop=drop,
                          backend=cfg.channel_backend)

    # ---- flight recorder (repro.obs; absent => compiled out) --------------
    # st[...] still holds the tick-entry values here (locals were rebound,
    # the dict is only updated below), so the masks are true transitions.
    tr = st.get("tr")
    if tr is not None or "mon_io" in st:
        sent_any = sends[0].mask
        for s in sends[1:]:
            sent_any = sent_any | s.mask
        cut = jnp.sum(sent_any & drop, axis=1)
    if tr is not None:
        es = obs.DEFAULT_SPEC
        vchg = v_cur != st["v_cur"]
        tr = obs.record(es, tr, "view_change", vchg, t, a=v_cur, b=r_cur)
        tr = obs.record(es, tr, "leader_change", vchg, t,
                        a=_leader_of(v_cur, n), b=v_cur)
        # sync<->async transitions: a=1 entering the async path, 0 exiting
        tr = obs.record(es, tr, "mode_switch", is_async != st["is_async"],
                        t, a=is_async, b=v_cur)
        tr = obs.record(es, tr, "commit", commit_key > st["commit_key"], t,
                        a=commit_key, b=jnp.sum(cvc, axis=1))
        tr = obs.record_env(es, tr, alive, t, a=v_cur, b=r_cur,
                            dropped_links=cut)
        st["tr"] = tr
    if "mon_io" in st:
        st["mon_io"] = {"dropped": cut.astype(jnp.int32)}

    st.update(
        v_cur=v_cur, r_cur=r_cur, is_async=is_async, bh_key=bh_key,
        bh_vc=bh_vc.astype(jnp.int32), commit_key=commit_key,
        cvc=cvc.astype(jnp.int32), prop_key=prop_key,
        last_vote_trig=last_vote_trig, deadline=deadline,
        timeout_sent_v=timeout_sent_v, async_phase=async_phase, my_r=my_r,
        my_avc=my_avc.astype(jnp.int32), exited_view=exited_view,
        ac_tick=ac_tick, ac_v_seen=ac_v_seen, vote_st=vote_st, to_st=to_st,
        pa_st=pa_st, va_st=va_st, ac_st=ac_st, ring=ring)
    return st
