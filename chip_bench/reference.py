"""Plain reference of the simulator's semantics, in numpy.

It imports nothing of the program. Given a configuration file's settings
and a list of grid points (rate, seed, scenario and workload primitives),
it steps the same tick-level state machines (Mandator's dissemination
chains, Sporades' synchronous and asynchronous paths, monolithic
Multi-Paxos with request forwarding), the same delayed-delivery channels,
the same NIC serialization and the same per-point metric extraction, and
returns results under the keys the program's ``collect()`` uses.

The points of one call are stepped together along a leading batch axis:
one pass over the ticks serves every sampled point. Each channel is its
own ring of ``D`` arrival slots: a send at tick ``t`` with delay ``d``
arrives at ``t + clip(d, 1, D - 1)``, and sends that meet in one slot
merge by elementwise max (counters by sum). ``D`` is the grid's delay
horizon, the bound the program sizes its rings to.

Client arrivals are Poisson draws keyed by ``fold_in(PRNGKey(seed), t)``,
made with ``jax.random`` on the default device: data made from the seed,
like a model's weights.

``dtype`` is the float type of every float state and message; the
control runs the reference in ``bfloat16`` in place of float32.
"""
from __future__ import annotations

import math
from typing import Dict, List

import numpy as np

from chip_bench import ref_tables

RS = 1 << 14            # Sporades rank key: view * RS + round
MAX_VIEWS = 4096        # pre-drawn common coins
HORIZON_MARGIN = 16     # ring slots past the static delay bound
CANONICAL_HORIZON = 256
WARMUP_FRAC = 0.15
BUCKET_MS = 500.0
SKETCH_BINS = 64
I32 = np.int32


def n_ticks(cfg: dict) -> int:
    return int(cfg["sim_seconds"] * 1000 / cfg["tick_ms"])


def delays_ms(cfg: dict) -> np.ndarray:
    rtt = np.asarray(cfg["rtt_ms"], np.float64)
    n = cfg["n_replicas"]
    return rtt[:n, :n] / 2.0


def horizon(cfg: dict, scen_tabs) -> int:
    """Ring slots for a grid: static link delay + largest scenario delay +
    the NIC backlog of every lane's largest batch at the worst throttle +
    a margin, next power of two, at least the canonical 256."""
    extra, scale = 0.0, 1.0
    for tab in scen_tabs:
        e, s = ref_tables.scenario_bounds(tab)
        extra, scale = max(extra, e), min(scale, s)
    bytes_per_tick = cfg["nic_gbps"] * 1e9 / 8.0 * cfg["tick_ms"] / 1000.0
    big = max(cfg["batch_paxos"], cfg["batch_mandator"],
              cfg["batch_sporades"]) * cfg["request_bytes"] + 100.0
    backlog = (math.inf if scale <= 0 else
               max(1, cfg["mandator_lanes"]) * cfg["n_replicas"] * big
               / (bytes_per_tick * scale))
    bound = (np.max(delays_ms(cfg)) / cfg["tick_ms"] + extra + backlog
             + HORIZON_MARGIN)
    bound = min(float(bound), float(n_ticks(cfg) + 1))
    h = max(64, 1 << max(0, int(np.ceil(bound)) - 1).bit_length())
    return max(h, CANONICAL_HORIZON)


class Channel:
    """One message type's delayed-delivery slots for B points:
    values [B, D, n, n, P] and presence flags [B, D, n, n]."""

    def __init__(self, b: int, d: int, n: int, p: int, fdt,
                 additive: bool = False):
        self.fill = 0.0 if additive else -1.0
        self.additive = additive
        self.d = d
        self.val = np.full((b, d, n, n, p), self.fill, fdt)
        self.flag = np.zeros((b, d, n, n), bool)

    def pop(self, t: int):
        """(flags [B, snd, rcv], values [B, snd, rcv, P]) arriving at t;
        the slot is emptied."""
        s = t % self.d
        out = self.flag[:, s].copy(), self.val[:, s].copy()
        self.flag[:, s] = False
        self.val[:, s] = self.fill
        return out

    def send(self, t: int, payload, delay, mask) -> None:
        """payload [B, snd, rcv, P]; delay [B, snd, rcv] ticks; mask
        [B, snd, rcv] — the links that send (already without cut links)."""
        b, i, j = np.nonzero(mask)
        s = (t + np.clip(delay[b, i, j], 1, self.d - 1)) % self.d
        if self.additive:
            self.val[b, s, i, j] = self.val[b, s, i, j] + payload[b, i, j]
        else:
            self.val[b, s, i, j] = np.maximum(self.val[b, s, i, j],
                                              payload[b, i, j])
        self.flag[b, s, i, j] = True


def _rx(a):
    """[B, snd, rcv, ...] -> [B, rcv, snd, ...]."""
    return np.swapaxes(a, 1, 2)


def _merge(state, flags, payload):
    """Fold arrivals into a receiver's latest-state matrix [B, rcv, snd,
    P] by elementwise max."""
    return np.where(_rx(flags)[..., None], np.maximum(state, _rx(payload)),
                    state)


def _bcast(rows):
    """Per-sender payload [B, n, P] -> every receiver [B, n, n, P]."""
    b, n, p = rows.shape
    return np.broadcast_to(rows[:, :, None, :], (b, n, n, p))


def _pick(a, idx):
    """a[b, i, idx[b, i]] for a [B, n, n]."""
    return np.take_along_axis(a, idx[..., None], axis=2)[..., 0]


def _pick_row(a, idx):
    """a[b, i, idx[b, i], :] for a [B, n, n, P]."""
    return np.take_along_axis(a, idx[..., None, None], axis=2)[:, :, 0]


# ----------------------------------------------------------------- clients

class Clients:
    """Per-origin request buffers and batch records [B, n, T]."""

    def __init__(self, b, n, t_total, fdt):
        z = lambda: np.zeros((b, n), fdt)  # noqa: E731
        self.fdt = fdt
        self.buffer, self.tsum, self.last_t, self.cpu = z(), z(), z(), z()
        self.create_t = np.full((b, n, t_total), np.inf, fdt)
        self.arr_mean = np.zeros((b, n, t_total), fdt)
        self.count = np.zeros((b, n, t_total), fdt)

    def arrive(self, cnt, t):
        self.buffer = self.buffer + cnt
        self.tsum = self.tsum + cnt * self.fdt.type(t)

    def refill(self, per_tick):
        self.cpu = np.minimum(self.cpu + per_tick, self.fdt.type(1e7))

    def form(self, t, can_form, round_idx, batch_size, batch_ticks):
        f = self.fdt.type
        size_ok = self.buffer >= f(batch_size)
        time_ok = (f(t) - self.last_t >= f(batch_ticks)) & (self.buffer > 0)
        formed = can_form & (size_ok | time_ok) & (self.cpu >= f(1.0))
        count = np.where(formed, np.minimum(
            np.minimum(self.buffer, f(batch_size)), self.cpu), f(0.0))
        frac = np.where(self.buffer > 0,
                        count / np.maximum(self.buffer, f(1.0)), f(0.0))
        taken = self.tsum * frac
        mean = np.where(count > 0, taken / np.maximum(count, f(1.0)), f(0.0))
        bi, oi = np.nonzero(formed)
        r = np.clip(round_idx[bi, oi], 0, self.count.shape[2] - 1)
        self.create_t[bi, oi, r] = np.minimum(self.create_t[bi, oi, r], f(t))
        self.arr_mean[bi, oi, r] = self.arr_mean[bi, oi, r] + mean[bi, oi]
        self.count[bi, oi, r] = self.count[bi, oi, r] + count[bi, oi]
        self.buffer = self.buffer - count
        self.tsum = self.tsum - taken
        self.cpu = self.cpu - count
        self.last_t = np.where(formed, f(t), self.last_t)
        return formed, count


def _egress(busy, t, bytes_out, fdt):
    """NIC serialization in receiver order: (new busy [B, n], extra delay
    [B, n, n] ticks)."""
    tf = fdt.type(t)
    cum = np.cumsum(bytes_out, axis=2, dtype=fdt)
    start = np.maximum(busy, tf)
    return start + cum[..., -1], (start[..., None] + cum) - tf


# ---------------------------------------------------------------- Mandator

class Mandator:
    def __init__(self, cfg, b, d, t_total, fdt):
        n = cfg["n_replicas"]
        self.cfg, self.fdt = cfg, fdt
        self.own = np.zeros((b, n), I32)
        self.formed = np.zeros((b, n), I32)
        self.lcr = np.zeros((b, n, n), I32)
        self.seen = np.zeros((b, n, n), I32)
        self.vote_max = np.zeros((b, n, n), I32)
        self.busy = np.zeros((b, n), fdt)
        self.batch = Channel(b, d, n, 2, fdt)
        self.vote = Channel(b, d, n, 1, fdt)
        self.clients = Clients(b, n, t_total, fdt)

    def tick(self, t, env, draws):
        cfg, f = self.cfg, self.fdt.type
        n = cfg["n_replicas"]
        quorum = n - (n - 1) // 2
        alive, delays, drop = env["alive"], env["delay"], env["drop"]
        bfl, bpay = self.batch.pop(t)
        vfl, vpay = self.vote.pop(t)
        c = self.clients
        c.arrive(draws * alive, t)
        c.refill(env["cpu_per_tick"])

        # a new batch: remember its round and its creator's completed rounds
        st = _merge(np.stack([self.seen, self.lcr], -1).astype(self.fdt),
                    bfl, bpay)
        seen, lcr = st[..., 0].astype(I32), st[..., 1].astype(I32)
        # vote (cumulatively) for every batch seen this tick
        vote_mask = _rx(bfl) & alive[..., None]
        self.vote.send(t, seen.astype(self.fdt)[..., None],
                       delays.astype(I32), vote_mask & ~drop)

        # votes: rounds complete in order once n - f replicas voted
        vote_max = _merge(self.vote_max.astype(self.fdt)[..., None], vfl,
                          vpay)[..., 0].astype(I32)
        own = self.own
        for _ in range(cfg["mandator_lanes"]):
            nxt = own + 1
            votes = np.sum(vote_max >= nxt[..., None], axis=2)
            own = np.where((self.formed >= nxt) & (votes >= quorum), nxt, own)
        ar = np.arange(n)
        lcr[:, ar, ar] = own

        # form and broadcast the next batch while a lane is free
        can = alive & (self.formed - own < cfg["mandator_lanes"])
        formed, count = c.form(t, can, self.formed + 1, cfg["batch_mandator"],
                               cfg["max_batch_ms"] / cfg["tick_ms"])
        formed_round = np.where(formed, self.formed + 1, self.formed)
        size = (count * f(cfg["request_bytes"]) + f(100.0)) * formed
        bytes_out = np.broadcast_to(size[..., None], delays.shape) \
            / env["nic_rate"][..., None]
        busy, ser = _egress(self.busy, t, bytes_out, self.fdt)
        self.busy = np.where(formed, busy, self.busy)
        total = (delays + np.where(formed[..., None], ser, f(0.0))).astype(I32)
        pay = np.stack([formed_round, own], -1).astype(self.fdt)
        self.batch.send(t, _bcast(pay), total, formed[..., None] & ~drop)

        self.own, self.formed, self.lcr = own, formed_round, lcr
        self.seen, self.vote_max = seen, vote_max


# ---------------------------------------------------------------- Sporades

def coin_table(n: int) -> np.ndarray:
    """The common coin of every view: uniform in [0, n), keyed by view."""
    import jax
    import jax.numpy as jnp
    base = jax.random.PRNGKey(0)
    keys = jax.vmap(lambda v: jax.random.fold_in(base, v))(
        jnp.arange(MAX_VIEWS, dtype=jnp.uint32))
    return np.asarray(jax.vmap(
        lambda k: jax.random.randint(k, (), 0, n))(keys)).astype(I32)


class Sporades:
    def __init__(self, cfg, b, d, fdt, coins):
        n = cfg["n_replicas"]
        self.cfg, self.fdt, self.coins = cfg, fdt, coins
        z = lambda *s: np.zeros((b,) + s, I32)  # noqa: E731
        full = lambda v, *s: np.full((b,) + s, v, fdt)  # noqa: E731
        self.v, self.r = z(n), z(n)
        self.is_async = np.zeros((b, n), bool)
        self.bh_key, self.bh_vc = z(n), z(n, n)
        self.commit_key, self.cvc = z(n), z(n, n)
        self.last_vote_trig = np.full((b, n), -1, I32)
        self.deadline = full(cfg["view_timeout_ms"] / cfg["tick_ms"], n)
        self.timeout_sent_v = np.full((b, n), -1, I32)
        self.phase, self.my_r, self.my_avc = z(n), z(n), z(n, n)
        self.exited_view = np.full((b, n), -1, I32)
        self.ac_tick = full(np.inf, n, n)
        self.ac_v_seen = np.full((b, n, n), -1, I32)
        self.vote_st = full(0.0, n, n, 2 + n)
        self.to_st = full(-1.0, n, n, 2 + n)
        self.pa_st = full(-1.0, n, n, 1 + n)
        self.va_st = full(-1.0, n, n, n)
        self.ac_st = full(-1.0, n, n, 2 + n)
        self.ch = {"prop": Channel(b, d, n, 2 + 2 * n, fdt),
                   "vote": Channel(b, d, n, 2 + n, fdt),
                   "to": Channel(b, d, n, 2 + n, fdt),
                   "pa": Channel(b, d, n, 1 + n, fdt),
                   "va": Channel(b, d, n, n, fdt),
                   "ac": Channel(b, d, n, 2 + n, fdt)}

    def tick(self, t, env, lcr):
        cfg, fdt = self.cfg, self.fdt
        f = fdt.type
        n = cfg["n_replicas"]
        q = n - (n - 1) // 2
        alive, drop = env["alive"], env["drop"]
        delays = env["delay"].astype(I32)
        to_ticks = f(cfg["view_timeout_ms"] / cfg["tick_ms"])
        tf = f(t)
        rows = np.arange(n)
        lcr_f = lcr.astype(fdt)
        got = {k: c.pop(t) for k, c in self.ch.items()}
        out = []          # (channel, payload [B, n, n, P], delay, mask)

        def key(v, r):
            return v * RS + r

        def leader_of(v):
            return v % n

        def col(x):
            return x[..., None].astype(fdt)

        v, r, is_async = self.v, self.r, self.is_async
        bh_key, bh_vc = self.bh_key, self.bh_vc.astype(fdt)
        commit_key, cvc = self.commit_key, self.cvc.astype(fdt)
        deadline = self.deadline

        # 1) <propose>: adopt a higher-ranked block, vote to its leader
        pfl, ppay = got["prop"]
        afl = _rx(pfl)
        ps = np.max(np.where(afl[..., None], _rx(ppay), f(-1.0)), axis=2)
        pb_key, pc_key = ps[..., 0].astype(I32), ps[..., 1].astype(I32)
        p_vc, p_cvc = ps[..., 2:2 + n], ps[..., 2 + n:]
        accept = afl.any(axis=2) & alive & ~is_async & (pb_key > key(v, r))
        cvc = np.where(accept[..., None], np.maximum(cvc, p_cvc), cvc)
        commit_key = np.where(accept, np.maximum(commit_key, pc_key),
                              commit_key)
        v = np.where(accept, pb_key // RS, v)
        r = np.where(accept, pb_key % RS, r)
        bh_key = np.where(accept, pb_key, bh_key)
        bh_vc = np.where(accept[..., None], p_vc, bh_vc)
        deadline = np.where(accept, tf + to_ticks, deadline)
        vote_pay = np.concatenate([col(bh_key), col(bh_key), bh_vc], -1)
        vote_mask = accept[..., None] & (rows[None, None, :]
                                         == leader_of(v)[..., None])
        out.append(("vote", _bcast(vote_pay), delays, vote_mask))

        # 2) <vote>: the leader that gathers n - f equal votes commits the
        #    voted block if n - f voters hold it as block_high, and
        #    proposes the next block
        vfl, vpay = got["vote"]
        vote_st = _merge(self.vote_st, vfl, vpay)
        voted = vote_st[..., 0].astype(I32)
        kmax = voted.max(axis=2)
        match = voted == kmax[..., None]
        lead = (alive & ~is_async & (match.sum(axis=2) >= q)
                & (kmax >= key(v, r)) & (kmax > self.last_vote_trig)
                & (leader_of(kmax // RS) == rows))
        vbh = vote_st[..., 1].astype(I32)
        bh_new = np.max(np.where(match, vbh, -1), axis=2)
        bh_vc_new = np.max(np.where(match[..., None], vote_st[..., 2:],
                                    f(-1.0)), axis=2)
        lead_commit = lead & (np.sum(match & (vbh == kmax[..., None]),
                                     axis=2) >= q)
        commit_key = np.where(lead_commit, np.maximum(commit_key, kmax),
                              commit_key)
        cvc = np.where(lead_commit[..., None], np.maximum(cvc, bh_vc_new),
                       cvc)
        v = np.where(lead, kmax // RS, v)
        r = np.where(lead, kmax % RS, r)
        bh_key = np.where(lead, np.maximum(bh_key, bh_new), bh_key)
        bh_vc = np.where(lead[..., None], np.maximum(bh_vc, bh_vc_new), bh_vc)
        new_key = key(v, r + 1)
        prop_pay = np.concatenate([col(new_key), col(commit_key),
                                   np.maximum(lcr_f, bh_vc), cvc], -1)
        out.append(("prop", _bcast(prop_pay), delays,
                    np.broadcast_to(lead[..., None], delays.shape)))
        last_vote_trig = np.where(lead, kmax, self.last_vote_trig)

        # 3) view timer: broadcast <timeout> once per view
        fire = alive & ~is_async & (tf >= deadline) & (self.timeout_sent_v < v)
        to_pay = np.concatenate([col(v), col(bh_key), bh_vc], -1)
        out.append(("to", _bcast(to_pay), delays,
                    np.broadcast_to(fire[..., None], delays.shape)))
        timeout_sent_v = np.where(fire, v, self.timeout_sent_v)

        # 4) <timeout>: n - f for this view or later enter the async path
        #    and propose a height-1 async block
        tfl, tpay = got["to"]
        to_st = _merge(self.to_st, tfl, tpay)
        to_v = to_st[..., 0].astype(I32)
        tvmax = to_v.max(axis=2)
        tmatch = to_v == tvmax[..., None]
        enter = alive & ~is_async & (tmatch.sum(axis=2) >= q) & (tvmax >= v)
        tbh = np.max(np.where(tmatch, to_st[..., 1].astype(I32), -1), axis=2)
        tbh_vc = np.max(np.where(tmatch[..., None], to_st[..., 2:], f(-1.0)),
                        axis=2)
        bh_key = np.where(enter, np.maximum(bh_key, tbh), bh_key)
        bh_vc = np.where(enter[..., None], np.maximum(bh_vc, tbh_vc), bh_vc)
        v = np.where(enter, tvmax, v)
        r = np.where(enter, np.maximum(r, bh_key % RS), r)
        is_async = is_async | enter
        r1 = r + 1
        avc = np.maximum(lcr_f, bh_vc)
        pa1 = np.concatenate([col((v * 2 + 1) * RS + r1), avc], -1)
        out.append(("pa", _bcast(pa1), delays,
                    np.broadcast_to(enter[..., None], delays.shape)))
        phase = np.where(enter, 1, self.phase)
        my_r = np.where(enter, r1, self.my_r)
        my_avc = np.where(enter[..., None], avc, self.my_avc.astype(fdt))
        deadline = np.where(enter, f(np.inf), deadline)

        # 5) <propose-async>: vote for blocks of my view above my round
        pafl, papay = got["pa"]
        pa_st = _merge(self.pa_st, pafl, papay)
        pa_k = pa_st[..., 0].astype(I32)
        pa_vh = pa_k // RS
        pa_h = np.where(pa_vh % 2 == 1, 1, 2)
        pa_v = (pa_vh - pa_h) // 2
        pa_r = pa_k % RS
        va_vote = (_rx(pafl) & alive[..., None] & is_async[..., None]
                   & (pa_v == v[..., None]) & (pa_r > r[..., None]))
        va_fields = np.where(va_vote, pa_k.astype(fdt), f(-1.0))
        out.append(("va", _bcast(va_fields), delays,
                    np.broadcast_to(va_vote.any(axis=2)[..., None],
                                    delays.shape)))

        # 6) <vote-async>: height 1 -> height 2 (or adopt a height-1 block
        #    that gathered n - f votes), height 2 -> async-complete
        vafl, vapay = got["va"]
        va_st = _merge(self.va_st, vafl, vapay)
        va_own = np.diagonal(va_st, axis1=1, axis2=3).transpose(0, 2, 1) \
            .astype(I32)                                  # [B, rcv, voter]
        cnt_h1 = np.sum(va_own == ((v * 2 + 1) * RS + my_r)[..., None],
                        axis=2)
        cnt_h2 = np.sum(va_own == ((v * 2 + 2) * RS + my_r)[..., None],
                        axis=2)
        to_h2 = alive & is_async & (phase == 1) & (cnt_h1 >= q)
        va_all = va_st.astype(I32)                        # [B, rcv, voter, p]
        k_p = va_all.max(axis=2)
        cnt_p = np.sum(va_all == k_p[:, :, None, :], axis=2)
        kp_vh = k_p // RS
        adoptable = ((cnt_p >= q) & (kp_vh % 2 == 1)
                     & ((kp_vh - 1) // 2 == v[..., None])
                     & (k_p % RS >= my_r[..., None]))
        cand = np.where(adoptable, k_p, -1)
        adopt_key, adopt_p = cand.max(axis=2), cand.argmax(axis=2)
        adopt = alive & is_async & (phase == 1) & ~to_h2 & (adopt_key >= 0)
        adopt_vc = np.where((_pick(pa_k, adopt_p) == adopt_key)[..., None],
                            _pick_row(pa_st[..., 1:], adopt_p), my_avc)
        go_h2 = to_h2 | adopt
        r2 = np.where(adopt, adopt_key % RS + 1, my_r + 1)
        avc2 = np.maximum(lcr_f, np.where(adopt[..., None], adopt_vc, my_avc))
        pa2 = np.concatenate([col((v * 2 + 2) * RS + r2), avc2], -1)
        out.append(("pa", _bcast(pa2), delays,
                    np.broadcast_to(go_h2[..., None], delays.shape)))
        my_r = np.where(go_h2, r2, my_r)
        my_avc = np.where(go_h2[..., None], avc2, my_avc)
        phase = np.where(go_h2, 2, phase)
        to_ac = alive & is_async & (phase == 2) & (cnt_h2 >= q)
        ac_pay = np.concatenate([col(v), col(my_r), my_avc], -1)
        out.append(("ac", _bcast(ac_pay), delays,
                    np.broadcast_to(to_ac[..., None], delays.shape)))
        phase = np.where(to_ac, 3, phase)

        # 7) <async-complete>: n - f for this view exit it; commit the coin
        #    leader's height-2 block if its complete is among the first
        #    n - f to arrive, else take its height-2 block as block_high
        acfl, acpay = got["ac"]
        ac_st = _merge(self.ac_st, acfl, acpay)
        ac_v = ac_st[..., 0].astype(I32)
        newer = _rx(acfl) & (ac_v > self.ac_v_seen)
        ac_tick = np.where(newer, tf, self.ac_tick)
        ac_v_seen = np.where(newer, ac_v, self.ac_v_seen)
        acm = ac_v == v[..., None]
        exit_ = (alive & is_async & (acm.sum(axis=2) >= q)
                 & (self.exited_view < v))
        ldr = self.coins[np.clip(v, 0, MAX_VIEWS - 1)]
        tick_m = np.where(acm, ac_tick, f(np.inf))
        thr = np.sort(tick_m, axis=2)[..., q - 1]
        ldr_in = _pick(acm, ldr) & (_pick(tick_m, ldr) <= thr)
        ldr_r = _pick(ac_st[..., 1].astype(I32), ldr)
        ldr_vc = _pick_row(ac_st[..., 2:], ldr)
        commit = exit_ & ldr_in
        commit_key = np.where(commit, np.maximum(commit_key, key(v, ldr_r)),
                              commit_key)
        cvc = np.where(commit[..., None], np.maximum(cvc, ldr_vc), cvc)
        bh_key = np.where(commit, key(v, ldr_r), bh_key)
        bh_vc = np.where(commit[..., None], ldr_vc, bh_vc)
        bfall = (exit_ & ~ldr_in & (_pick(pa_v, ldr) == v)
                 & (_pick(pa_h, ldr) == 2))
        bh_key = np.where(bfall, key(v, _pick(pa_r, ldr)), bh_key)
        bh_vc = np.where(bfall[..., None], _pick_row(pa_st[..., 1:], ldr),
                         bh_vc)
        exited_view = np.where(exit_, v, self.exited_view)
        r = np.where(exit_, bh_key % RS, r)
        v = np.where(exit_, v + 1, v)
        is_async = is_async & ~exit_
        phase = np.where(exit_, 0, phase)
        deadline = np.where(exit_, tf + to_ticks, deadline)
        ex_pay = np.concatenate([col(key(v, r)), col(bh_key), bh_vc], -1)
        ex_mask = exit_[..., None] & (rows[None, None, :]
                                      == leader_of(v)[..., None])
        out.append(("vote", _bcast(ex_pay), delays, ex_mask))

        for name, pay, dly, mask in out:
            self.ch[name].send(t, pay, dly, mask & ~drop)

        self.v, self.r, self.is_async = v, r, is_async
        self.bh_key, self.bh_vc = bh_key, bh_vc.astype(I32)
        self.commit_key, self.cvc = commit_key, cvc.astype(I32)
        self.last_vote_trig, self.deadline = last_vote_trig, deadline
        self.timeout_sent_v = timeout_sent_v
        self.phase, self.my_r = phase, my_r
        self.my_avc, self.exited_view = my_avc.astype(I32), exited_view
        self.ac_tick, self.ac_v_seen = ac_tick, ac_v_seen
        self.vote_st, self.to_st, self.pa_st = vote_st, to_st, pa_st
        self.va_st, self.ac_st = va_st, ac_st


# ------------------------------------------------------------- Multi-Paxos

class MultiPaxos:
    """Monolithic Multi-Paxos: clients forward to the leader, which runs
    one slot at a time with the request batch inside the accept."""

    def __init__(self, cfg, b, d, t_total, fdt):
        n = cfg["n_replicas"]
        self.cfg, self.fdt = cfg, fdt
        self.view = np.zeros((b, n), I32)
        self.last_heard = np.zeros((b, n), fdt)
        self.ready_at = np.zeros((b, n), fdt)
        self.slot = np.zeros((b, n), I32)
        self.outstanding = np.zeros((b, n), bool)
        self.acks = np.zeros((b, n, n), I32)
        self.committed = np.zeros((b, n), I32)
        self.busy = np.zeros((b, n), fdt)
        d_ticks = delays_ms(cfg) / cfg["tick_ms"]
        maj = n // 2 + 1
        self.phase1 = np.sort(2 * d_ticks, axis=1)[:, maj - 1].astype(fdt)
        self.fw = Channel(b, d, n, 2, fdt, additive=True)
        self.acc = Channel(b, d, n, 3 + n, fdt)
        self.ack = Channel(b, d, n, 1, fdt)
        self.clients = Clients(b, n, t_total, fdt)

    def tick(self, t, env, draws):
        cfg, fdt = self.cfg, self.fdt
        f = fdt.type
        n = cfg["n_replicas"]
        maj = n // 2 + 1
        alive, drop = env["alive"], env["drop"]
        delays = env["delay"].astype(I32)
        to_ticks = f(cfg["view_timeout_ms"] / cfg["tick_ms"])
        tf = f(t)
        rows = np.arange(n)
        view = self.view
        leader = view % n
        is_leader = (leader == rows) & alive
        ffl, fpay = self.fw.pop(t)
        afl, apay = self.ack.pop(t)
        cfl, cpay = self.acc.pop(t)
        c = self.clients
        c.refill(env["cpu_per_tick"])
        c.arrive(draws * alive, t)

        # forward the whole local buffer to my leader (the leader keeps
        # its own); a cut link keeps the requests buffered for a retry
        fw_mask = ((rows[None, None, :] == leader[..., None])
                   & alive[..., None] & (c.buffer > 0)[..., None]
                   & (rows[None, :] != leader)[..., None])
        sent_mask = fw_mask & ~drop
        self.fw.send(t, _bcast(np.stack([c.buffer, c.tsum], -1)), delays,
                     sent_mask)
        sent = sent_mask.any(axis=2)
        c.buffer = np.where(sent, f(0.0), c.buffer)
        c.tsum = np.where(sent, f(0.0), c.tsum)
        pool = np.sum(np.where(ffl[..., None], fpay, f(0.0)), axis=1,
                      dtype=fdt)
        c.buffer = c.buffer + pool[..., 0]
        c.tsum = c.tsum + pool[..., 1]

        # acks: the leader commits its slot on a majority
        acks = _merge(self.acks.astype(fdt)[..., None], afl,
                      apay)[..., 0].astype(I32)
        commit = (is_leader & self.outstanding
                  & (np.sum(acks >= self.slot[..., None], axis=2) >= maj))
        committed = np.where(commit, self.slot, self.committed)
        outstanding = self.outstanding & ~commit

        # the leader starts the next slot with a new batch
        can = is_leader & ~outstanding & (tf >= self.ready_at)
        formed, count = c.form(t, can, self.slot + 1, cfg["batch_paxos"],
                               cfg["max_batch_ms"] / cfg["tick_ms"])
        slot = np.where(formed, self.slot + 1, self.slot)
        size = np.where(formed, count * f(cfg["request_bytes"]) + f(100.0),
                        f(0.0))
        outstanding = outstanding | formed
        bytes_out = np.broadcast_to(size[..., None], delays.shape) \
            / env["nic_rate"][..., None]
        busy, ser = _egress(self.busy, t, bytes_out, fdt)
        self.busy = np.where(formed, busy, self.busy)
        total = (delays.astype(fdt)
                 + np.where(formed[..., None], ser, f(0.0))).astype(I32)
        b = view.shape[0]
        acc_pay = np.concatenate([view[..., None].astype(fdt),
                                  slot[..., None].astype(fdt),
                                  np.zeros((b, n, 1 + n), fdt)], -1)
        self.acc.send(t, _bcast(acc_pay), total, formed[..., None] & ~drop)

        # followers: a fresh accept (re)sets the view and is acked
        a2 = _rx(cfl)
        mx = np.max(np.where(a2[..., None], _rx(cpay), f(-1.0)), axis=2)
        acc_view, acc_slot = mx[..., 0].astype(I32), mx[..., 1].astype(I32)
        fresh = a2.any(axis=2) & (acc_view >= view) & alive
        view = np.where(fresh, acc_view, view)
        last_heard = np.where(fresh, tf, self.last_heard)
        ack_mask = fresh[..., None] & (rows[None, None, :]
                                       == (view % n)[..., None])
        ack_pay = np.broadcast_to(acc_slot.astype(fdt)[..., None, None],
                                  (b, n, n, 1))
        self.ack.send(t, ack_pay, delays, ack_mask & ~drop)

        # silence past the view timeout: next view, rotating leader
        expired = alive & (tf - last_heard > to_ticks)
        view = np.where(expired, view + 1, view)
        last_heard = np.where(expired, tf, last_heard)
        became = expired & ((view % n) == rows)
        self.ready_at = np.where(became, tf + self.phase1, self.ready_at)
        self.view, self.last_heard, self.slot = view, last_heard, slot
        self.outstanding, self.acks, self.committed = outstanding, acks, \
            committed


# ----------------------------------------------------------------- metrics

def _wquantile(vals, weights, q, fdt):
    order = np.argsort(vals, kind="stable")
    v, w = vals[order], weights[order]
    cum = np.cumsum(w, dtype=fdt)
    tot = cum[-1]
    if not tot > 0:
        return fdt.type(np.nan)
    cdf = cum / tot
    idx = min(int(np.searchsorted(cdf, fdt.type(q), side="left")),
              len(v) - 1)
    return v[idx]


def _commit_ticks(trace, r_max, fdt):
    """trace [T, n] monotone committed rounds -> [n, r_max] tick at which
    round r (1-based) commits, inf if never."""
    rs = np.arange(r_max)
    out = np.full((trace.shape[1], r_max), np.inf, fdt)
    for o in range(trace.shape[1]):
        idx = np.searchsorted(trace[:, o], rs, side="left")
        ok = (idx < trace.shape[0]) & (rs >= 1)
        out[o, ok] = idx[ok]
    return out


def _sketch(lat, w, fdt):
    """64 equal-probability rank buckets: weighted-mean centre and weight
    of each (empty buckets: +inf centre, weight 0)."""
    order = np.argsort(lat, kind="stable")
    v, w = lat[order], w[order]
    cum = np.cumsum(w, dtype=fdt)
    tot = cum[-1]
    mid = (cum - fdt.type(0.5) * w) / (tot if tot > 0 else fdt.type(1.0))
    b = np.clip((mid * fdt.type(SKETCH_BINS)).astype(I32), 0,
                SKETCH_BINS - 1)
    wsum = np.zeros((SKETCH_BINS,), fdt)
    vsum = np.zeros((SKETCH_BINS,), fdt)
    np.add.at(wsum, b, w)
    np.add.at(vsum, b, np.where(w > 0, w * np.where(w > 0, v, 0), 0))
    with np.errstate(invalid="ignore", divide="ignore"):
        centre = np.where(wsum > 0, vsum / np.where(wsum > 0, wsum, 1),
                          np.inf).astype(fdt)
    return {"v": centre, "w": wsum}


def point_metrics(cfg, create_t, arr_mean, count, commit_t, fdt,
                  reduced=False) -> Dict:
    """Throughput, latency quantiles and timelines of one point's batch
    records [n, R] (ticks; latency in ms)."""
    f = fdt.type
    ticks = n_ticks(cfg)
    ok = np.isfinite(commit_t) & (count > 0) & np.isfinite(create_t)
    with np.errstate(invalid="ignore"):
        lat = (commit_t - arr_mean) * f(cfg["tick_ms"])
    w0 = WARMUP_FRAC * ticks
    in_win = ok & (commit_t >= f(w0))
    win_s = (ticks - w0) * cfg["tick_ms"] / 1000.0
    w = np.where(in_win, count, f(0.0))
    out = {"throughput": np.sum(w.ravel(), dtype=fdt) / f(win_s),
           "median_ms": _wquantile(lat.ravel(), w.ravel(), 0.5, fdt),
           "p99_ms": _wquantile(lat.ravel(), w.ravel(), 0.99, fdt)}
    cnt_ok = np.where(ok, count, f(0.0))
    out["committed"] = np.sum(cnt_ok.ravel(), dtype=fdt)
    if reduced:
        out["sketch"] = _sketch(lat.ravel(), w.ravel(), fdt)
        return out
    nb = int(np.ceil(ticks * cfg["tick_ms"] / BUCKET_MS))
    with np.errstate(invalid="ignore"):
        b = np.where(ok, commit_t * f(cfg["tick_ms"] / BUCKET_MS), f(0.0))
    b = np.clip(b.astype(I32), 0, nb - 1)
    tl = np.zeros((nb,), fdt)
    np.add.at(tl, b.ravel(), cnt_ok.ravel())
    out["timeline"] = tl / f(BUCKET_MS / 1000.0)
    n = count.shape[0]
    out["origin_median_ms"] = np.array(
        [_wquantile(lat[o], w[o], 0.5, fdt) for o in range(n)], fdt)
    out["origin_p99_ms"] = np.array(
        [_wquantile(lat[o], w[o], 0.99, fdt) for o in range(n)], fdt)
    rows = np.broadcast_to(np.arange(n)[:, None], b.shape)
    tl_o = np.zeros((n, nb), fdt)
    np.add.at(tl_o, (rows, b), cnt_ok)
    lat_sum = np.zeros((n, nb), fdt)
    np.add.at(lat_sum, (rows, b), cnt_ok * np.where(ok, lat, f(0.0)))
    out["origin_timeline"] = tl_o / f(BUCKET_MS / 1000.0)
    with np.errstate(invalid="ignore", divide="ignore"):
        out["origin_lat_ms_timeline"] = np.where(
            tl_o > 0, lat_sum / np.maximum(tl_o, f(1e-9)), np.nan).astype(fdt)
    return out


# -------------------------------------------------------------------- run

def _draws(seeds, lam):
    """Poisson client arrivals [B, T, n] for each point's seed."""
    import jax
    import jax.numpy as jnp

    def one(seed, lam_t):
        base = jax.random.PRNGKey(seed)
        keys = jax.vmap(lambda t: jax.random.fold_in(base, t))(
            jnp.arange(lam_t.shape[0], dtype=jnp.int32))
        return jax.vmap(jax.random.poisson)(keys, lam_t)

    out = jax.jit(jax.vmap(one))(jnp.asarray(np.asarray(seeds, np.int32)),
                                 jnp.asarray(lam))
    return np.asarray(out).astype(np.float32)


def simulate(cfg: dict, points: List[dict], d: int, reduced: bool = False,
             dtype=np.float32) -> List[Dict]:
    """Run every point (``rate`` tx/s, ``seed``, ``scenario`` and
    ``workload`` primitive lists) of one protocol; ``d`` is the grid's
    ring horizon. Returns one result dict per point."""
    fdt = np.dtype(dtype)
    f = fdt.type
    proto = cfg["protocol"]
    n, ticks, b = cfg["n_replicas"], n_ticks(cfg), len(points)
    tick_ms = cfg["tick_ms"]
    scen = [ref_tables.scenario_tables(p["scenario"], n, tick_ms, ticks)
            for p in points]
    wl = [ref_tables.workload_tables(p["workload"], n, tick_ms, ticks)
          for p in points]
    # per-tick environment of every point
    alive = np.stack([s["alive"][s["win_of_tick"]] for s in scen], 1)
    drop = np.stack([s["drop"][s["win_of_tick"]] for s in scen], 1)
    base = np.asarray(delays_ms(cfg) / tick_ms, np.float32)
    delay = np.stack([base + s["extra_delay"][s["win_of_tick"]]
                      for s in scen], 1).astype(fdt)
    bytes_per_tick = np.float32(cfg["nic_gbps"] * 1e9 / 8.0 * tick_ms
                                / 1000.0)
    nic = np.stack([bytes_per_tick * s["nic_scale"][s["win_of_tick"]]
                    for s in scen], 1).astype(fdt)
    rate = np.array([(p["rate"] * tick_ms / 1000.0 / n) for p in points],
                    np.float64).astype(np.float32)
    trivial = all(w["trivial"] for w in wl)
    lam = np.stack([np.broadcast_to(r, (ticks, n)) if trivial
                    else r * w["rate_of"][w["win_of_tick"]]
                    for r, w in zip(rate, wl)]).astype(np.float32)
    draws = _draws([p["seed"] for p in points], lam).astype(fdt)
    cpu = f(np.float32(tick_ms * 1000.0 / cfg["cpu_us_per_request"]))

    if proto == "multipaxos":
        px = MultiPaxos(cfg, b, d, ticks, fdt)
        clients = px.clients
    else:
        m = Mandator(cfg, b, d, ticks, fdt)
        clients = m.clients
        sp = Sporades(cfg, b, d, fdt, coin_table(n))
    trace = np.zeros((ticks, b, n), I32)
    if proto == "mandator-sporades":
        cvc_all = np.zeros((ticks, b, n, n), I32)
        commit_key = np.zeros((ticks, b, n), I32)
        is_async = np.zeros((ticks, b, n), bool)
        views = np.zeros((b,), I32)
    for t in range(ticks):
        env = {"alive": alive[t], "drop": drop[t], "delay": delay[t],
               "nic_rate": nic[t], "cpu_per_tick": cpu}
        if proto == "multipaxos":
            px.tick(t, env, draws[:, t])
            trace[t] = px.committed
        else:
            m.tick(t, env, draws[:, t])
            sp.tick(t, env, m.lcr)
            trace[t] = sp.cvc.max(axis=1)
            cvc_all[t], commit_key[t] = sp.cvc, sp.commit_key
            is_async[t] = sp.is_async
            views = np.maximum(views, sp.v.max(axis=1))
    results = []
    for i in range(b):
        ct = _commit_ticks(trace[:, i], ticks, fdt)
        r = point_metrics(cfg, clients.create_t[i], clients.arr_mean[i],
                          clients.count[i], ct, fdt, reduced=reduced)
        if proto == "mandator-sporades":
            r["async_frac"] = np.mean(is_async[:, i].astype(fdt), dtype=fdt)
            r["views"] = int(views[i])
            if not reduced:
                r["cvc_all"] = cvc_all[:, i]
                r["commit_key"] = commit_key[:, i]
        results.append(r)
    return results
