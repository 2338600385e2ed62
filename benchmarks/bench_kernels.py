"""Channel-ring micro-benchmark: wall time of the packed ring's tick loop
against the seed per-channel substrate, on whatever backend JAX resolves
(the Pallas ring commit on a TPU, the pure-jnp scatter oracle elsewhere)."""
from __future__ import annotations

import time
from typing import List, Tuple

import jax
import jax.numpy as jnp

Row = Tuple[str, float, str]


def _time(fn, *args, reps: int = 5) -> float:
    # warm up (compile) exactly once; block_until_ready handles pytrees
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps * 1e6


def _channel_setup(n: int = 5):
    """Shared inputs of the channel microbench: sporades-shaped ring spec
    plus deterministic payload/delay/mask tensors (fixed PRNG keys, so the
    timed programs and the roofline HLO analysis lower the same bytes)."""
    from repro.core import sporades

    spec = sporades.ring_spec(n)
    widths = [(c.name, c.width) for c in spec.channels]
    key = jax.random.PRNGKey(0)
    delays = jax.random.randint(jax.random.PRNGKey(1), (n, n), 1, 170
                                ).astype(jnp.int32)
    payloads = {name: jax.random.uniform(jax.random.fold_in(key, i),
                                         (n, n, w), jnp.float32, 0.0, 9.0)
                for i, (name, w) in enumerate(widths)}
    mask = jnp.ones((n, n), jnp.bool_)
    return spec, widths, payloads, delays, mask


def packed_loop_fn(dmax: int = 256, n: int = 5, ticks: int = 200):
    """The packed-ring tick loop as a no-arg jittable callable — the
    channel microbench's packed path; benchmarks/roofline.py lowers the
    same callable for the HLO cost + roofline block."""
    from repro.core import channel as ch

    spec, widths, payloads, delays, mask = _channel_setup(n)

    def loop():
        ring = ch.make_ring(spec, dmax, n)

        def step(carry, t):
            msgs = ch.ring_deliver(spec, carry, t)
            out = sum(jnp.sum(p) + jnp.sum(f) for f, p in msgs.values())
            sends = [ch.Send(name, payloads[name], delays, mask)
                     for name, _ in widths]
            # "auto" = what the simulator dispatches: Pallas kernel on
            # TPU, jnp scatter oracle elsewhere
            return ch.ring_commit(spec, carry, t, sends,
                                  backend="auto"), out

        return jax.lax.scan(step, ring, jnp.arange(ticks, dtype=jnp.int32))

    return loop


def bench_channel(ticks: int = 200) -> List[Row]:
    """Packed channel ring vs the seed per-channel substrate: one scanned
    tick loop of sporades-shaped traffic (6 channels, broadcast sends) per
    substrate, at the auto-resolved baseline horizon and the seed-era 2048.
    Rows report us per simulated tick; run.py also drops the comparison
    into benchmarks/artifacts/channel_bench.json."""
    from repro.core import channel as ch
    from repro.core import compile_cache

    compile_cache.ensure()   # microbench compiles hit the persistent cache
    n = 5
    spec, widths, payloads, delays, mask = _channel_setup(n)

    def legacy_loop(dmax):
        chans = {name: ch.make_channel(dmax, n, w) for name, w in widths}

        def step(carry, t):
            out = 0.0
            new = {}
            for name, _ in widths:
                c, fl, pay = ch.deliver(carry[name], t)
                c = ch.send(c, t, payloads[name], delays, mask)
                out = out + jnp.sum(pay) + jnp.sum(fl)
                new[name] = c
            return new, out

        return jax.lax.scan(step, chans, jnp.arange(ticks, dtype=jnp.int32))

    rows: List[Row] = []
    for dmax in (256, 2048):
        t_leg = _time(jax.jit(lambda d=dmax: legacy_loop(d))) / ticks
        t_pak = _time(jax.jit(packed_loop_fn(dmax, n, ticks))) / ticks
        rows.append((f"channel/legacy_D{dmax}", t_leg,
                     f"substrate=per-channel;n={n};channels={len(widths)}"))
        rows.append((f"channel/packed_D{dmax}", t_pak,
                     f"substrate=packed-ring;n={n};K={spec.k};"
                     f"speedup={t_leg / t_pak:.2f}x"))
    return rows
