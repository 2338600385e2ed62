"""Dispatch wrapper for the fused channel-ring commit.

Called from ``core/channel.ring_commit`` inside the (already-jitted) tick
scan, so there is no jit here — just backend selection and the reshaping
each backend wants. The pure-jnp oracle (ref.py) is the CPU default and
the correctness oracle; the Pallas kernel (kernel.py) is the TPU path.

Backends: ``"jnp"`` (alias ``"ref"``), ``"pallas"`` (the compiled kernel,
TPU only), ``"pallas-interpret"`` (the kernel under the Pallas
interpreter, for parity tests), ``"auto"`` (pallas on TPU, jnp
elsewhere).
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.channel_ring.kernel import ring_commit_tpu
from repro.kernels.channel_ring.ref import ring_commit_ref

BACKENDS = ("auto", "jnp", "ref", "pallas", "pallas-interpret")

# static per-entry layout: (payload offset, width, flag field, additive)
EntryLayout = Tuple[int, int, int, bool]

# per-tick send entry, already mask-merged: (slot [n,n] int32,
# vals [n,n,w] float32 with merge-neutral at masked-out links,
# flag [n,n] float32 1.0/0.0)
Entry = Tuple[jax.Array, jax.Array, jax.Array]


def target_platform() -> str:
    """Platform that a program traced now runs on: the device set by
    ``jax.default_device`` if any, else the default backend."""
    dev = jax.config.jax_default_device
    if dev is None:
        return jax.default_backend()
    return dev if isinstance(dev, str) else dev.platform


def tiled_ring_bytes(d: int, n: int, k: int) -> int:
    """Bytes of one lane's ``[d, n, n, k]`` f32 ring as the TPU lays it
    out, its last two axes in (8, 128) tiles: what the compiler weighs
    when it keeps a scan's ring in VMEM (``experiment.RING_VMEM_BYTES``)."""
    return d * n * (-(-n // 8) * 8) * (-(-k // 128) * 128) * 4


def resolve_backend(backend: str) -> str:
    if backend not in BACKENDS:
        raise ValueError(f"unknown channel backend {backend!r}; "
                         f"one of {BACKENDS}")
    if backend == "auto":
        return "pallas" if target_platform() == "tpu" else "jnp"
    return "jnp" if backend == "ref" else backend


def _scatter_args(entries: Sequence[Entry], layout: Sequence[EntryLayout]):
    """Concatenate the per-entry contributions along the field axis into
    the oracle's flat (slots, field-index, values) triples — max group
    (each max-merged send's contiguous payload+flag fields, plus every
    additive send's flag) and add group (additive payloads)."""
    sm, fm, vm = [], [], []
    sa, fa, va = [], [], []
    for (slot, vals, flag), (off, w, flag_off, additive) in zip(entries,
                                                                layout):
        if additive:
            sa.append(jnp.broadcast_to(slot[..., None], vals.shape))
            # lint: allow(traced-purity): field indices come from the
            # static EntryLayout — trace-time constants, not host data
            fa.append(np.arange(off, off + w))
            va.append(vals)
            sm.append(slot[..., None])
            # lint: allow(traced-purity): static EntryLayout flag index
            fm.append(np.array([flag_off]))
            vm.append(flag[..., None])
        else:
            # payload + flag are contiguous: one [n, n, w+1] block
            sm.append(jnp.broadcast_to(slot[..., None],
                                       slot.shape + (w + 1,)))
            # lint: allow(traced-purity): static EntryLayout field span
            fm.append(np.arange(off, off + w + 1))
            vm.append(jnp.concatenate([vals, flag[..., None]], axis=-1))
    cat = lambda xs: jnp.concatenate(xs, axis=-1)  # noqa: E731
    # lint: allow(traced-purity): concatenating the static index vectors
    # stays host-side; only jnp.asarray crosses to the device
    out = (cat(sm), jnp.asarray(np.concatenate(fm), jnp.int32), cat(vm))
    if sa:
        # lint: allow(traced-purity): static index vector (see above)
        return out + (cat(sa), jnp.asarray(np.concatenate(fa), jnp.int32),
                      cat(va))
    return out + (None, None, None)


def _planes(entries: Sequence[Entry], layout: Sequence[EntryLayout],
            k: int):
    """Expand the entries into full-width merge planes for the kernel:
    ``(tgt [P, n, n, K] int32, val [P, n, n, K] f32, adds)``. Channels own
    disjoint field windows, so the p-th send of every channel shares max
    plane p; additive payloads share one add plane (one send per additive
    channel per tick). Fields no send of a plane covers get target -1."""
    max_planes: list = []        # per plane: {field offset: (slot, vals)}
    add_plane: dict = {}
    for (slot, vals, flag), (off, w, flag_off, additive) in zip(entries,
                                                                layout):
        if additive:
            add_plane[off] = (slot, vals)
            pieces = {flag_off: (slot, flag[..., None])}
        else:
            pieces = {off: (slot, jnp.concatenate([vals, flag[..., None]],
                                                  axis=-1))}
        for plane in max_planes:
            if not set(pieces) & set(plane):
                plane.update(pieces)
                break
        else:
            max_planes.append(dict(pieces))
    n = entries[0][0].shape[0]
    tgts, vals = [], []
    for plane in max_planes + ([add_plane] if add_plane else []):
        tgt = jnp.full((n, n, k), -1, jnp.int32)
        val = jnp.zeros((n, n, k), jnp.float32)
        for off, (slot, v) in plane.items():
            w = v.shape[-1]
            tgt = tgt.at[..., off:off + w].set(slot[..., None])
            val = val.at[..., off:off + w].set(v)
        tgts.append(tgt)
        vals.append(val)
    adds = (False,) * len(max_planes) + ((True,) if add_plane else ())
    return jnp.stack(tgts), jnp.stack(vals), adds


def ring_commit(buf: jax.Array, t: jax.Array, fill: jax.Array,
                entries: Sequence[Entry], layout: Sequence[EntryLayout],
                backend: str = "auto") -> jax.Array:
    """Fused commit of one tick's sends: slot-clear of the delivered slot
    ``t % D`` + one scatter-max + one scatter-add (see ref.py)."""
    backend = resolve_backend(backend)
    if backend == "jnp":
        return ring_commit_ref(buf, t, fill,
                               *_scatter_args(entries, layout))
    tgt, val, adds = _planes(entries, layout, buf.shape[-1])
    return ring_commit_tpu(buf, t, fill, tgt, val, adds,
                           interpret=backend == "pallas-interpret")
