"""Smoke run of the simulator's main path on a TPU.

    python chip_smoke.py             # one chip: phases (a)-(e)
    python chip_smoke.py --chips 4   # four chips: the mesh-sharded grid only

Drives ``experiment.run_sweep`` at the paper's §5 deployment (n = 5
regions, the default ``SMRConfig``, 4 s simulations) with the default
``channel_backend="auto"``, which on a TPU is the compiled Pallas
ring-commit kernel:

  (a) device check: the default backend is a TPU and "auto" resolves to
      the Pallas kernel;
  (b) main path: mandator-sporades at the fig6 rates under ``baseline``
      (256-slot ring) and under ``paper-ddos`` (1,024-slot ring), and
      multipaxos at its fig6 rates (additive ``fw`` channel);
  (c) the same grids with ``channel_backend="jnp"`` on the chip, which
      must be bitwise equal to (b);
  (d) the same grids on the host CPU with ``channel_backend="jnp"``,
      compared with (b) within ``CPU_TOLERANCE``;
  (e) one paper-ddos point with the health monitor at "full", which must
      report no invariant violation.

``--chips 4`` runs a 64-point grid through ``dispatch_sweep(mesh=4)``
against the same grid on a 1-device mesh (bitwise equal), and checks that
every device holds a quarter of the points.

Seconds printed are set-up (trace, compile, dispatch) and run (execution
and readback) of a cold process: smoke timings, not benchmark numbers.
The last line of standard output is one JSON object naming the device;
any failed phase exits non-zero before it is printed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

SRC = Path(__file__).resolve().parent / "src"
sys.path.insert(0, str(SRC))

SIM_SECONDS = 4.0
MS_RATES = (50_000, 150_000, 300_000, 450_000)    # fig6, mandator-sporades
MP_RATES = (10_000, 30_000, 50_000, 100_000)      # fig6, multipaxos
SCALARS = ("throughput", "median_ms", "p99_ms", "committed")
ARRAYS = ("cvc_all", "commit_key")

# CPU reference (d) against the chip (b): largest relative difference
# admitted per output; keys not listed must be bitwise equal. The
# protocol state (cvc_all, commit_key) agrees bitwise. The request counts
# do not: ``jax.random.poisson`` rounds its transcendentals differently on
# the TPU, and on a v5e 3 to 41 of 20,000 draws (at 10 to 90 requests per
# tick) differ from the host's. The worst differences over the three
# grids there were 2.9e-4 (throughput, committed) and 6.4e-4 (median_ms,
# p99_ms); the bounds are about three times that.
CPU_TOLERANCE = {"throughput": 1e-3, "committed": 1e-3,
                 "median_ms": 2e-3, "p99_ms": 2e-3}


def _log(msg: str) -> None:
    print(msg, flush=True)


def _fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def _grids(sim_s: float):
    from repro.core.experiment import SweepSpec
    from repro.scenarios import library as scenario_library
    return (
        ("mandator-sporades/baseline", "mandator-sporades", 256,
         SweepSpec(rates=MS_RATES,
                   scenarios=(scenario_library.get("baseline", sim_s),))),
        ("mandator-sporades/paper-ddos", "mandator-sporades", 1024,
         SweepSpec(rates=MS_RATES,
                   scenarios=(scenario_library.get("paper-ddos", sim_s),))),
        ("multipaxos/baseline", "multipaxos", 256,
         SweepSpec(rates=MP_RATES,
                   scenarios=(scenario_library.get("baseline", sim_s),))),
    )


def _sweep(tag: str, protocol: str, cfg, spec, **kw):
    """run_sweep with its set-up/run split printed."""
    from repro.core import experiment
    experiment.reset_timing_stats()
    t0 = time.perf_counter()
    rows = experiment.run_sweep(protocol, cfg, spec, **kw)
    wall = time.perf_counter() - t0
    st = experiment.timing_stats()[protocol]
    _log(f"  {tag}: {len(rows)} points, ring {st['horizon']} slots, "
         f"set-up {st['compile_s']:.3f} s, run {st['run_s']:.3f} s, "
         f"wall {wall:.3f} s")
    return rows, st


def _values(row: dict, key: str):
    return np.asarray(row[key], np.float64)


def _bitwise(a, b) -> bool:
    return a.shape == b.shape and np.array_equal(a, b, equal_nan=True)


def _rel_diff(a, b) -> float:
    """Largest elementwise |a - b| / max(|a|, |b|); inf where the NaNs or
    the shapes differ."""
    if a.shape != b.shape or not np.array_equal(np.isnan(a), np.isnan(b)):
        return float("inf")
    a, b = a[~np.isnan(a)], b[~np.isnan(b)]
    with np.errstate(invalid="ignore"):
        d = np.where(a == b, 0.0, np.abs(a - b)
                     / np.maximum(np.abs(a), np.abs(b)))
    return float(np.max(d, initial=0.0))


def _poisson_mismatch(lam: float, cpu) -> int:
    """Draws of ``jax.random.poisson`` (one replica-tick each, 4 s of
    ticks at n = 5) that differ between the chip and the host CPU."""
    import jax
    draw = jax.jit(lambda k: jax.random.poisson(k, lam, (4000, 5)))
    key = jax.random.PRNGKey(0)
    on_chip = np.asarray(draw(key))
    on_cpu = np.asarray(draw(jax.device_put(key, cpu)))
    return int(np.sum(on_chip != on_cpu))


def _keys(row: dict):
    return SCALARS + tuple(k for k in ARRAYS if k in row)


def _check_main(tag: str, rows, horizon: int, st: dict) -> None:
    if st["horizon"] != horizon:
        _fail(f"{tag}: ring has {st['horizon']} slots, expected {horizon}")
    for r in rows:
        if not (np.isfinite(r["throughput"]) and r["throughput"] > 0
                and r["committed"] > 0 and np.isfinite(r["median_ms"])
                and np.isfinite(r["p99_ms"])):
            _fail(f"{tag}@{r['rate']:.0f}: no progress or non-finite "
                  f"metrics {[r[k] for k in SCALARS]}")
        for k in ARRAYS:
            if k in r and not np.all(np.isfinite(np.asarray(r[k]))):
                _fail(f"{tag}@{r['rate']:.0f}: non-finite {k}")
        _log(f"    rate {r['rate']:>9.0f}: throughput {r['throughput']!r} "
             f"tx/s, median {r['median_ms']!r} ms, p99 {r['p99_ms']!r} ms, "
             f"committed {r['committed']!r}")


def one_chip() -> None:
    import jax

    from repro.configs.smr import SMRConfig
    from repro.kernels.channel_ring.ops import resolve_backend
    from repro.obs import monitor as obs_monitor

    backend = resolve_backend("auto")
    if backend != "pallas":
        _fail(f'channel_backend "auto" resolves to {backend!r} on the '
              f"chip, not the Pallas kernel")
    _log('(a) device check passed: "auto" resolves to the Pallas kernel')

    grids = _grids(SIM_SECONDS)
    cfg = SMRConfig(sim_seconds=SIM_SECONDS)
    _log(f"(b) main path, {cfg.channel_backend} backend, "
         f"{SIM_SECONDS} s simulations")
    main = {}
    for tag, proto, horizon, spec in grids:
        rows, st = _sweep(tag, proto, cfg, spec)
        _check_main(tag, rows, horizon, st)
        main[tag] = rows

    _log('(c) channel_backend="jnp" on the chip, bitwise against (b)')
    jcfg = SMRConfig(sim_seconds=SIM_SECONDS, channel_backend="jnp")
    for tag, proto, _, spec in grids:
        rows, _ = _sweep(tag, proto, jcfg, spec)
        for a, b in zip(main[tag], rows):
            for k in _keys(a):
                if not _bitwise(_values(a, k), _values(b, k)):
                    _fail(f"{tag}@{a['rate']:.0f}: {k} differs between "
                          f"pallas and jnp on the chip "
                          f"(rel {_rel_diff(_values(a, k), _values(b, k))})")
    _log("    pallas == jnp bitwise on every point and output")

    _log('(d) host CPU reference, channel_backend="jnp"')
    cpu = jax.devices("cpu")[0]
    worst: dict = {}
    with jax.default_device(cpu):
        for tag, proto, _, spec in grids:
            rows, _ = _sweep(tag, proto, jcfg, spec)
            for a, b in zip(main[tag], rows):
                for k in _keys(a):
                    va, vb = _values(a, k), _values(b, k)
                    d = 0.0 if _bitwise(va, vb) else _rel_diff(va, vb)
                    worst[k] = max(worst.get(k, 0.0), d)
                    if d > CPU_TOLERANCE.get(k, 0.0):
                        _log(f"    {tag}@{a['rate']:.0f}: {k} rel diff "
                             f"{d!r} > tolerance "
                             f"{CPU_TOLERANCE.get(k, 0.0)!r}")
    for k, d in sorted(worst.items()):
        _log(f"    {k}: " + ("bitwise equal" if d == 0.0
                              else f"max rel diff {d!r}"))
    for rate in MS_RATES:
        lam = rate / 1000.0 / 5
        _log(f"    Poisson draws at {lam:g} requests/tick: "
             f"{_poisson_mismatch(lam, cpu)} of 20000 differ chip vs CPU")
    # judged after (e), so that one run reports every phase
    cpu_over = [k for k, d in worst.items()
                if d > CPU_TOLERANCE.get(k, 0.0)]

    _log('(e) health monitor at "full", paper-ddos')
    mcfg = SMRConfig(sim_seconds=SIM_SECONDS, monitor_level="full")
    tag, proto, _, spec = grids[1]
    spec = type(spec)(rates=(300_000,), scenarios=spec.scenarios)
    rows, _ = _sweep(tag, proto, mcfg, spec)
    v = obs_monitor.verdict(rows[0])
    _log(f"    {obs_monitor.format_verdict(v)}")
    if v is None or v["level"] != "full" or not v["ok"] or v["violations"]:
        _fail(f"monitor verdict {v}")
    if not np.isfinite(rows[0]["throughput"]) or rows[0]["committed"] <= 0:
        _fail("monitored point made no progress")
    if cpu_over:
        _fail(f"(d) CPU reference outside its tolerance for {cpu_over}")


def four_chips() -> None:
    from repro.configs.smr import SMRConfig
    from repro.core import experiment
    from repro.core.experiment import SweepSpec
    from repro.scenarios import library as scenario_library

    cfg = SMRConfig(sim_seconds=SIM_SECONDS)
    spec = SweepSpec(rates=tuple(np.linspace(50_000, 450_000, 16)),
                     seeds=(0, 1, 2, 3),
                     scenarios=(scenario_library.get("baseline",
                                                     SIM_SECONDS),))
    _log(f"mesh-sharded grid: {spec.size} points of mandator-sporades, "
         f"{SIM_SECONDS} s simulations")
    res = {}
    for m in (4, 1):
        experiment.reset_timing_stats()
        t0 = time.perf_counter()
        pending = experiment.dispatch_sweep("mandator-sporades", cfg, spec,
                                            mesh=m)
        where = pending.point_devices()
        rows = pending.collect()
        wall = time.perf_counter() - t0
        st = experiment.timing_stats()["mandator-sporades"]
        per_dev = {d: where.count(d) for d in sorted(set(where))}
        _log(f"  mesh={m}: points per device {per_dev}, "
             f"set-up {st['compile_s']:.3f} s, run {st['run_s']:.3f} s, "
             f"wall {wall:.3f} s")
        if len(per_dev) != m or set(per_dev.values()) != {spec.size // m}:
            _fail(f"mesh={m}: points are not spread evenly over {m} "
                  f"devices: {per_dev}")
        res[m] = rows
    for a, b in zip(res[4], res[1]):
        for k in SCALARS:
            if not _bitwise(_values(a, k), _values(b, k)):
                _fail(f"rate {a['rate']:.0f} seed {a['seed']}: {k} "
                      f"differs, 4 devices {a[k]!r} vs 1 device {b[k]!r}")
        for k in ("v", "w"):
            if not _bitwise(np.asarray(a["sketch"][k], np.float64),
                            np.asarray(b["sketch"][k], np.float64)):
                _fail(f"rate {a['rate']:.0f} seed {a['seed']}: latency "
                      f"sketch {k} differs between 4 and 1 devices")
        if not (np.isfinite(a["throughput"]) and a["committed"] > 0):
            _fail(f"rate {a['rate']:.0f} seed {a['seed']}: no progress")
    _log(f"  4-device results == 1-device results bitwise on all "
         f"{spec.size} points")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases (a)-(e); 4: the mesh-sharded grid")
    args = ap.parse_args()
    # phase (d) needs the host CPU backend next to the TPU
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"
    import jax

    t0 = time.perf_counter()
    devs = jax.devices()
    _log(f"jax {jax.__version__}, devices: {devs}")
    dev = devs[0]
    if dev.platform != "tpu":
        _fail(f"no TPU: JAX's default backend is {dev.platform!r}")
    if len(devs) != args.chips:
        _fail(f"asked for {args.chips} chip(s), JAX reports {len(devs)}")
    if args.chips == 4:
        four_chips()
    else:
        one_chip()
    _log(f"all phases passed in {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
