"""Fused channel-ring commit as a Pallas-TPU kernel.

XLA lowers the oracle's scatters (ref.py) to serialized scatter ops. This
kernel re-expresses the whole tick as a *dense* pass over the ring
instead: the grid tiles the slot axis, each step holds a ``[bs, n, n, K]``
block of the packed ring in VMEM and

  - resets the delivered slot ``t % D`` to the fill vector,
  - for every merge plane (see below) compares the plane's target-slot
    array against the block's slot ids and max- or add-merges the plane's
    values where they match.

A *plane* is a ``[n, n, K]`` pair (target slot, value) at full field
width, built by the wrapper (ops.py): every field of the ring is either
covered by one send's contribution or holds target ``-1``, which matches
no slot. Channels occupy disjoint field windows, so the sends of distinct
channels share one plane, and a plane only repeats for a channel sent
more than once in a tick. The kernel body is therefore pure elementwise
``==``/``maximum``/``+``/``where`` over whole blocks — no scatter and no
lane slicing, which the TPU lowering refuses (``scatter-max``, unaligned
lane windows).

Work is O(D * n^2 * K) dense VPU ops per tick. The result is bitwise equal
to the oracle: each merge is the same f32 ``max``/``+`` the scatter
applies, at the same (slot, i, j, field) positions, and an additive field
is written by at most one send per tick (channel.ring_commit asserts it).
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Upper bound on one [bs, n, n, K] ring block in VMEM. The in and out
# blocks are double-buffered (4 blocks), which keeps the kernel well
# inside v5e's 16 MiB default scoped VMEM.
BLOCK_BYTES = 2 << 20


def _padded_slot_bytes(n: int, k: int) -> int:
    """VMEM bytes of one ring slot ``[n, n, K]`` f32: the two minor dims
    pad to the (8, 128) tile."""
    return n * (-(-n // 8) * 8) * (-(-k // 128) * 128) * 4


def block_slots(d: int, n: int, k: int) -> int:
    """Slots per grid step: the largest power of two that divides ``d``
    and keeps one block under ``BLOCK_BYTES``."""
    bs = 1
    while (d % (2 * bs) == 0
           and 2 * bs * _padded_slot_bytes(n, k) <= BLOCK_BYTES):
        bs *= 2
    return bs


def _commit_kernel(t_ref, buf_ref, fill_ref, tgt_ref, val_ref, out_ref, *,
                   bs: int, adds: Sequence[bool]):
    # slot ids of this block, [bs, 1, 1, 1]
    s = (pl.program_id(0) * bs
         + jax.lax.broadcasted_iota(jnp.int32, (bs, 1, 1, 1), 0))
    # slot-clear of the tick's delivered slot (t_ref holds t % D)
    blk = jnp.where(s == t_ref[0], fill_ref[...], buf_ref[...])
    for p, additive in enumerate(adds):
        hit = tgt_ref[p:p + 1] == s                      # [bs, n, n, K]
        val = val_ref[p:p + 1]                           # [1, n, n, K]
        merged = blk + val if additive else jnp.maximum(blk, val)
        blk = jnp.where(hit, merged, blk)
    out_ref[...] = blk


def ring_commit_tpu(buf: jax.Array, t: jax.Array, fill: jax.Array,
                    tgt: jax.Array, val: jax.Array, adds: Sequence[bool],
                    *, bs: int | None = None,
                    interpret: bool = False) -> jax.Array:
    """buf: [D, n, n, K]; t: scalar int32; fill: [K]; tgt: [P, n, n, K]
    int32 target slot per plane and field (-1 = untouched); val:
    [P, n, n, K] f32 merge values; adds[p]: plane p add-merges (else
    max-merges). ``bs`` overrides the slots per grid step."""
    d, n, _, k = buf.shape
    if bs is None:
        bs = block_slots(d, n, k)
    assert d % bs == 0, (d, bs)
    kernel = functools.partial(_commit_kernel, bs=bs, adds=tuple(adds))
    blk = pl.BlockSpec((bs, n, n, k), lambda i: (i, 0, 0, 0))
    whole = lambda shape: pl.BlockSpec(  # noqa: E731
        shape, lambda i: (0,) * len(shape))
    return pl.pallas_call(
        kernel,
        grid=(d // bs,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), blk,
                  whole((1, n, n, k)), whole(tgt.shape), whole(val.shape)],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct(buf.shape, buf.dtype),
        input_output_aliases={1: 0},
        interpret=interpret,
    )(jnp.reshape(t % d, (1,)).astype(jnp.int32), buf,
      jnp.broadcast_to(fill, (1, n, n, k)), tgt, val)
