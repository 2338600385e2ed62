"""Delayed-delivery message channels for the tick-based WAN simulator.

A channel is a ring buffer ``[Dmax, n, n, P]`` of payload vectors plus a
presence flag ``[Dmax, n, n]``; sender i's message to j written at arrival
slot ``(t + delay_ij) % Dmax``. All protocol payloads are designed to be
*monotone* (elementwise-max mergeable) — colliding deliveries merge into
the later state, which an omission-fault-tolerant protocol tolerates by
construction (DESIGN.md §8). The receive side folds arrivals into a
"latest state" matrix with elementwise max.

Two substrates share those semantics:

- the seed-era **per-channel** API (``make_channel``/``send``/``deliver``)
  — one ring dict per message type, 2 scatters + 1 clear per channel per
  tick; kept as the reference the packed path is pinned against
  (tests/test_channel.py);
- the **packed ring** (``RingSpec``/``make_ring``/``ring_deliver``/
  ``ring_commit``) — ALL of a protocol's channels concatenated along the
  field axis into one ``[Dmax, n, n, K]`` buffer (one flag field per
  channel), so a whole tick's traffic is one fused scatter-max + one
  scatter-add (additive counter channels) + one slot-clear, dispatched
  through ``repro.kernels.channel_ring`` (jnp oracle on CPU, Pallas dense
  kernel on TPU). Bitwise-equal to the per-channel path by construction:
  same slots, same merge ops, same neutral elements.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.channel_ring import ops as ring_ops

NEG = -1.0  # "absent" payload fill


def make_channel(dmax: int, n: int, p: int, additive: bool = False
                 ) -> Dict[str, jax.Array]:
    fill = 0.0 if additive else NEG
    return {
        "buf": jnp.full((dmax, n, n, p), fill, jnp.float32),
        "flag": jnp.zeros((dmax, n, n), jnp.bool_),
        "fill": jnp.float32(fill),
    }


def send(ch: Dict[str, jax.Array], t: jax.Array, payload: jax.Array,
         delay_ticks: jax.Array, mask: jax.Array, additive: bool = False,
         drop: jax.Array | None = None) -> Dict[str, jax.Array]:
    """payload: [n, n, P] (sender, receiver, fields); delay_ticks: [n, n]
    int32 >= 1; mask: [n, n] bool — which (i, j) actually send this tick.
    drop: optional [n, n] bool — links the network scenario cuts this tick
    (netsim.link_drop); a dropped send is a silent omission, which the
    monotone-payload protocols tolerate by construction.
    Merging policy: elementwise max (monotone payloads) or add (counters)."""
    if drop is not None:
        mask = mask & ~drop
    dmax = ch["buf"].shape[0]
    n = payload.shape[0]
    slot = (t + jnp.clip(delay_ticks, 1, dmax - 1)) % dmax          # [n, n]
    ii, jj = jnp.meshgrid(jnp.arange(n), jnp.arange(n), indexing="ij")
    if additive:
        merged = jnp.where(mask[..., None], payload, 0.0)
        buf = ch["buf"].at[slot, ii, jj].add(merged)
    else:
        merged = jnp.where(mask[..., None], payload, NEG)
        buf = ch["buf"].at[slot, ii, jj].max(merged)
    flag = ch["flag"].at[slot, ii, jj].max(mask)
    return {"buf": buf, "flag": flag, "fill": ch["fill"]}


def deliver(ch: Dict[str, jax.Array], t: jax.Array
            ) -> Tuple[Dict[str, jax.Array], jax.Array, jax.Array]:
    """Pop slot t. Returns (channel, flags [n,n], payload [n,n,P])."""
    dmax = ch["buf"].shape[0]
    slot = t % dmax
    flags = ch["flag"][slot]
    payload = ch["buf"][slot]
    buf = ch["buf"].at[slot].set(ch["fill"])
    flag = ch["flag"].at[slot].set(False)
    return {"buf": buf, "flag": flag, "fill": ch["fill"]}, flags, payload


def fold_state(state: jax.Array, flags: jax.Array, payload: jax.Array
               ) -> jax.Array:
    """Merge arrivals into latest-state matrix [n, n, P] (receiver, sender)."""
    # payload is (sender, receiver, P) -> transpose to (receiver, sender, P)
    arr = jnp.swapaxes(payload, 0, 1)
    fl = jnp.swapaxes(flags, 0, 1)[..., None]
    return jnp.where(fl, jnp.maximum(state, arr), state)


# --------------------------------------------------------------------------
# Packed ring: one fused delivery ring per protocol
# --------------------------------------------------------------------------

class ChannelSpec(NamedTuple):
    """One logical channel inside a packed ring."""
    name: str
    width: int                 # payload fields
    additive: bool = False     # add-merge (counters) instead of max-merge


@dataclass(frozen=True)
class RingSpec:
    """Static field layout of a protocol's packed ring.

    Channels are laid out in declaration order, each as its payload fields
    immediately followed by its own flag field —
      K = sum(width_c + 1)
    — so one send's whole contribution (payload + flag) is a single
    contiguous window of the field axis, which is what lets the fused
    commit scatter wide rows instead of single fields. Max-merged payload
    fields clear to ``NEG``; additive payload fields and all flag fields
    clear to 0.0 (flags merge by max either way).
    """
    channels: Tuple[ChannelSpec, ...]

    def __init__(self, *channels: ChannelSpec):
        object.__setattr__(self, "channels", tuple(channels))
        assert len({c.name for c in channels}) == len(channels), channels

    @property
    def k(self) -> int:
        return sum(c.width + 1 for c in self.channels)

    def offset(self, name: str) -> int:
        off = 0
        for c in self.channels:
            if c.name == name:
                return off
            off += c.width + 1
        raise KeyError(name)

    def flag(self, name: str) -> int:
        return self.offset(name) + self[name].width

    def __getitem__(self, name: str) -> ChannelSpec:
        for c in self.channels:
            if c.name == name:
                return c
        raise KeyError(name)

    def fill(self) -> np.ndarray:
        """Per-field clear value [K]: merge-neutral of each field."""
        # lint: allow(traced-purity): the ring layout is static — this
        # numpy vector is built once per trace and constant-folds into
        # the compiled program
        f = np.zeros((self.k,), np.float32)
        for c in self.channels:
            if not c.additive:
                f[self.offset(c.name):self.offset(c.name) + c.width] = NEG
        return f

    def layout(self, name: str) -> Tuple[int, int, int, bool]:
        """(payload offset, width, flag field, additive) — the static
        per-entry layout the kernels consume."""
        c = self[name]
        return (self.offset(name), c.width, self.flag(name), c.additive)


def ring_occupancy(spec: RingSpec, ring: Dict[str, jax.Array]) -> jax.Array:
    """Fraction of (slot, sender, receiver, channel) entries currently
    holding an undelivered message — the flag fields are >0.5 exactly
    while a send waits in its arrival slot, so this is a direct in-flight
    occupancy gauge of the delivery ring (repro.obs.monitor)."""
    flags = jnp.stack([ring["buf"][..., spec.flag(c.name)]
                       for c in spec.channels], axis=-1)
    return jnp.mean((flags > 0.5).astype(jnp.float32))


class Send(NamedTuple):
    """One buffered send of a tick: channel name + the legacy ``send``
    arguments. The per-tick send list of a protocol is static (same
    channels in the same order every tick), so it lowers to a fixed fused
    scatter."""
    name: str
    payload: jax.Array         # [n, n, P]
    delay_ticks: jax.Array     # [n, n] int32 >= 1 (clipped like send())
    mask: jax.Array            # [n, n] bool


def make_ring(spec: RingSpec, dmax: int, n: int) -> Dict[str, jax.Array]:
    fill = jnp.asarray(spec.fill())
    return {"buf": jnp.broadcast_to(fill, (dmax, n, n, spec.k)
                                    ).astype(jnp.float32)}


def ring_deliver(spec: RingSpec, ring: Dict[str, jax.Array], t: jax.Array
                 ) -> Dict[str, Tuple[jax.Array, jax.Array]]:
    """Read slot t of every channel at once (one gather). Returns
    {name: (flags [n, n] bool, payload [n, n, P])} — identical to what the
    per-channel ``deliver`` returns for each channel. The slot is NOT
    cleared here; ``ring_commit`` clears it (sends never target slot t, so
    the clear commutes across the tick). Runs under the ``ring_deliver``
    named scope."""
    with jax.named_scope("ring_deliver"):
        slot = ring["buf"][t % ring["buf"].shape[0]]         # [n, n, K]
        out = {}
        for c in spec.channels:
            off = spec.offset(c.name)
            out[c.name] = (slot[..., spec.flag(c.name)] > 0.5,
                           slot[..., off:off + c.width])
        return out


def ring_commit(spec: RingSpec, ring: Dict[str, jax.Array], t: jax.Array,
                sends: List[Send], drop: jax.Array | None = None,
                backend: str = "auto") -> Dict[str, jax.Array]:
    """Fused commit of one tick: clear the delivered slot ``t % Dmax`` and
    merge every buffered send — one scatter-max (+ one scatter-add if the
    spec has additive channels), via repro.kernels.channel_ring. ``drop``
    is the tick's scenario link-cut mask, applied to every send (silent
    omission), exactly as the per-channel path passed it to ``send``.
    Runs under the ``ring_commit`` named scope, whatever the backend."""
    with jax.named_scope("ring_commit"):
        dmax = ring["buf"].shape[0]
        # the fused scatter-add sums duplicate rows in one op, which float
        # non-associativity could tell apart from sequential per-send adds —
        # the bitwise-equivalence contract therefore requires additive
        # channels to send at most once per tick (max-merged channels may
        # repeat freely: max is order-free)
        add_names = [s.name for s in sends if spec[s.name].additive]
        assert len(add_names) == len(set(add_names)), \
            f"additive channel sent twice in one tick: {add_names}"
        entries, layout = [], []
        for s in sends:
            c = spec[s.name]
            mask = s.mask if drop is None else s.mask & ~drop
            slot = (t + jnp.clip(s.delay_ticks, 1, dmax - 1)) % dmax
            neutral = 0.0 if c.additive else NEG
            vals = jnp.where(mask[..., None], s.payload, neutral)
            entries.append((slot, vals, mask.astype(jnp.float32)))
            layout.append(spec.layout(s.name))
        buf = ring_ops.ring_commit(ring["buf"], t, jnp.asarray(spec.fill()),
                                   entries, layout, backend=backend)
        return {"buf": buf}
