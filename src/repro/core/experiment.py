"""Batched experiment engine: an entire workload × scenario × rate × seed
sweep grid as ONE compiled JAX program per protocol.

The paper's headline results (Figs. 6–9) are sweeps over arrival rate,
protocol, and network scenario — and, beyond the paper, over *traffic
shape* (``repro.workloads``). Instead of re-tracing the tick-level
``jax.lax.scan`` for every grid point, ``run_sweep`` lowers a ``SweepSpec``
to a single ``jax.vmap``-over-scan dispatch:

  1. the channel delay horizon is resolved ONCE for the whole sweep
     (``netsim.resolve_horizon`` over every scenario in the grid) so all
     points share one ring shape — the packed channel rings are then
     exactly as large as the sweep's true delay bound;
  2. every scenario variant becomes an array-native env
     (``netsim.build_env`` with a common window-table pad), stacked
     leaf-wise — and every workload variant becomes a windowed rate table
     (``workloads.lower``, same pad-and-stack trick);
  3. the cartesian grid is flattened to B points, each an
     (env, workload-table, rate, seed) tuple gathered from the stacks;
  4. ``harness.sim_point`` — scan *plus* on-device metric extraction — is
     vmapped over the B axis and jitted once per
     (protocol, cfg, workload-mode, B) shape.

The analytic baselines (epaxos / rabia) have no tick loop; they are looped
on the host behind the same API (time-varying rates come from the same
compiled tables via ``workloads.analytic``) so callers can sweep any
protocol.

**Canonical program signatures.** Tracing + XLA-compiling a sweep program
dominates total wall-clock (BENCH_core.json: >=95% of every fig suite), so
``run_sweep`` canonicalizes program *shapes* by default: the
scenario/workload window tables round up to power-of-two floors, the
auto-resolved ring horizon to ``netsim.CANONICAL_HORIZON``, and the
grid runs as async dispatches of chunks whose widths ``_lane_chunks``
takes from the grid size, the target platform and the program's ring
size: on the TPU, greedy powers of two of at most ``LANE_CAP`` lanes
whose rings fit the VMEM the compiler keeps them in (a 20-point fig 6
grid is two 8-lane launches and one 4-lane launch), elsewhere one
``CANONICAL_LANES``-wide launch a point. No chunk is padded, and a
one-point grid is the one-lane program everywhere. Every sweep with the
same replica count, tick count, ring horizon, and workload mode — the
fig 6/7/9 suites, the robustness and workload matrices, every
``run_sim`` single point — therefore reuses one compiled program per
protocol and chunk width instead of compiling per-suite shape variants.
Canonicalization is inert by construction (vmap lanes are independent,
pad window rows are never indexed, a larger ring never clips a valid
delivery), and tests/test_scenarios.py pins canonical == native bitwise.
Pass ``canonical=False`` to lower and dispatch the whole grid at its
native width.

**Compile accounting.** ``trace_counts()`` exposes how many times each
protocol's program was traced — the equivalence tests
(tests/test_experiment.py, tests/test_workloads.py) pin a whole grid to
one trace — ``program_signatures()`` the distinct compiled signatures per
protocol (tests/test_compile_cache.py pins figs 6/7/9 to one), and
``timing_stats()`` the compile-vs-run wall-clock split, the host spans
of every grid (``lower``, ``enqueue``, ``readback``, ``decode``: seconds
and counts, each also a ``jax.profiler.TraceAnnotation`` named
``experiment.<span>`` on the device trace's clock) plus the resolved ring
horizon. ``compile_report()`` joins all of that with the
persistent-cache counters (``repro.core.compile_cache``), which
benchmarks/run.py persists per suite to BENCH_core.json. Every sweep also
``compile_cache.ensure()``s the persistent cache, so repeat processes pay
XLA compile once ever.
"""
from __future__ import annotations

import itertools
import time
from dataclasses import dataclass, replace
from functools import partial
from typing import Dict, Iterator, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import workloads as wlc
from repro.configs.smr import SMRConfig
from repro.core import compile_cache, harness, netsim
from repro.distributed import mesh as dmesh
from repro.kernels.channel_ring import ops as ring_ops

ANALYTIC_PROTOCOLS = ("epaxos", "rabia")

# Canonical program width of a one-point grid, and of every chunk off the
# TPU: ONE lane. On the CPU a canonical sweep executes its grid as
# per-point dispatches of the single-lane program, so a 1-point run_sim,
# a 4-rate fig sweep, and a 16-cell robustness matrix share one
# executable with zero padded (wasted) device work — padding the batch
# axis instead was measured at up to 4x execution wall on single-point
# sweeps. Window rows DO pad (rows are cheap: they are never indexed past
# the real count) to a power-of-two floor so a baseline (W=1) and a crash
# schedule (W=3) share one program.
CANONICAL_LANES = 1
# Widest chunk of a canonical grid on the TPU (a power of two; see
# ``_lane_chunks``). A tick's ops work on arrays of a vector register or
# two, so their time is set by their count more than their size: on one
# v5e a point's device time falls from 210 ms at one lane to 100 ms a
# lane at 8 lanes (fig 6's Mandator-Sporades; Multi-Paxos 97 -> 43 ms),
# and 16 lanes were no faster (PERF.md, section 6: the width sweep).
LANE_CAP = 8
# Padded bytes of a chunk's packed rings (``ring_ops.tiled_ring_bytes``,
# every lane, every ring) that the v5e compiler keeps in VMEM across the
# tick scan: 80 MiB stay there (8 Mandator-Sporades lanes at 256 slots,
# 2 at 1,024 or at n = 9), 144 MiB do not. A ring that falls out to HBM
# makes the ring-commit kernel stream it every tick, 6-8x slower a lane
# than in VMEM, so a chunk narrows until its rings fit.
RING_VMEM_BYTES = 80 << 20
# Window-table floor of 32 rows covers every library scenario and workload
# at both --quick (2s) and full (4s) sim lengths (gray-wan tops out at 30
# windows at 4s), so the fig suites AND the robustness matrix lower to the
# same scenario-window axis — one compiled program instead of a per-suite
# shape split (the robustness suite previously missed the cache on a
# 16-row variant).
CANONICAL_MIN_WINDOWS = 32

_TRACE_COUNTS: Dict[str, int] = {}
_TIMING: Dict[str, Dict[str, float]] = {}
# the host spans of one grid, in order: scenario/workload tables to the
# stacked inputs; program lookup plus the launches (with the inputs'
# transfer to the device); the results' transfer to the host; result rows
SPANS = ("lower", "enqueue", "readback", "decode")
_SIGNATURES: Dict[str, set] = {}


@dataclass(frozen=True, order=True)
class ProgramSignature:
    """The static shape key of one compiled sweep program. Two sweeps with
    equal signatures (and equal protocol / cfg statics / workload mode)
    hit the same jit cache entry — zero new traces, zero new compiles."""
    n: int             # replicas
    ticks: int         # scan length (sim_seconds / tick_ms)
    lanes: int         # compiled batch width: a chunk's (_lane_chunks),
                       # or the grid size with canonical=False
    scen_windows: int  # scenario window-table rows (padded)
    wl_windows: int    # workload window-table rows (padded)
    horizon: int       # channel-ring slots (Dmax)
    trivial: bool      # workload-mode statics
    closed: bool


def _canon_pow2(x: int, floor: int) -> int:
    """Next power of two >= x, floored at ``floor``."""
    return max(floor, 1 << (max(1, x) - 1).bit_length())


def _lane_chunks(n_points: int, platform: str,
                 lane_bytes: int = 0) -> List[int]:
    """Widths of the chunks a canonical grid of ``n_points`` runs as, in
    grid order. On the TPU: greedy powers of two of at most ``LANE_CAP``
    lanes whose rings, ``lane_bytes`` a lane, fit ``RING_VMEM_BYTES``
    (20 points at 8 lanes -> [8, 8, 4]), so no lane is padded and one
    program serves each width. Elsewhere: one ``CANONICAL_LANES``-wide
    chunk a point."""
    if platform != "tpu":
        return [CANONICAL_LANES] * n_points
    cap = LANE_CAP
    while cap > 1 and cap * lane_bytes > RING_VMEM_BYTES:
        cap //= 2
    chunks = []
    while n_points:
        chunks.append(min(cap, 1 << (n_points.bit_length() - 1)))
        n_points -= chunks[-1]
    return chunks


def _lane_ring_bytes(protocol: str, cfg: SMRConfig) -> int:
    """Padded bytes of one lane's packed rings on the TPU."""
    n, d = cfg.n_replicas, int(cfg.delay_horizon_ticks)
    return sum(ring_ops.tiled_ring_bytes(d, n, spec.k)
               for spec in harness.ring_specs(protocol, n))


def trace_counts() -> Dict[str, int]:
    """jit traces of the sweep program per protocol since the last reset."""
    return dict(_TRACE_COUNTS)


def reset_trace_counts() -> None:
    """Reset the per-protocol trace counters and signature sets (the jit
    cache itself is untouched — a reused program still counts 0 traces)."""
    _TRACE_COUNTS.clear()
    _SIGNATURES.clear()
    _SHARD_SIGNATURES.clear()


def program_signatures() -> Dict[str, tuple]:
    """Distinct ``ProgramSignature``s lowered per protocol since the last
    ``reset_trace_counts()`` — the test oracle for "these suites share one
    compiled program"."""
    return {p: tuple(sorted(s)) for p, s in _SIGNATURES.items()}


def compile_report() -> Dict:
    """First-class compile accounting: per-protocol traces and distinct
    program signatures (since the last reset) plus the process-wide
    persistent-cache counters (hits/misses, backend-compile seconds,
    compile seconds saved). benchmarks/run.py snapshots this per suite
    into BENCH_core.json."""
    return {
        "traces": trace_counts(),
        "programs": {p: len(s) for p, s in _SIGNATURES.items()},
        "signatures": program_signatures(),
        "cache": compile_cache.stats(),
    }


def timing_stats() -> Dict[str, Dict[str, float]]:
    """Per-protocol wall-clock of the sweep dispatches since the last
    reset: ``<span>_s`` and ``<span>_n``, the seconds and count of each
    host span in ``SPANS``; ``compile_s`` (the ``enqueue`` spans that
    traced: trace + lower + backend compile or persistent-cache load —
    execution is excluded because dispatch is async), ``run_s`` (the
    other ``enqueue`` spans plus every ``readback`` span, which waits for
    execution), ``dispatches``, ``horizon`` (the resolved ring size
    of the latest sweep), ``by_shape`` (the dispatches by program shape,
    keyed ``n{replicas}.d{horizon}``: one process may run several),
    ``by_lanes`` (the launches of unsharded programs by batch width,
    keyed by the width as a string: a 16-point fig 6 grid on the TPU is
    ``{"8": 2}``, on the CPU ``{"1": 16}``), and ``arrivals_hoisted``
    / ``arrivals_in_scan`` (the programs built, by where they draw the
    arrivals)."""
    return {k: {**v, "by_shape": dict(v["by_shape"]),
                "by_lanes": dict(v["by_lanes"])}
            for k, v in _TIMING.items()}


def reset_timing_stats() -> None:
    _TIMING.clear()


def _stats(protocol: str) -> Dict[str, float]:
    st = _TIMING.get(protocol)
    if st is None:
        st = _TIMING[protocol] = {"compile_s": 0.0, "run_s": 0.0,
                                  "dispatches": 0, "horizon": 0,
                                  "by_shape": {}, "by_lanes": {},
                                  "arrivals_hoisted": 0,
                                  "arrivals_in_scan": 0}
        for name in SPANS:
            st[f"{name}_s"], st[f"{name}_n"] = 0.0, 0
    return st


class _span:
    """One host span of a grid: a ``TraceAnnotation`` named
    ``experiment.<name>`` (so a profiler trace shows it on the device
    trace's clock) whose ``perf_counter`` seconds and count add to
    ``timing_stats()``. Off the profiler it costs a few microseconds."""

    def __init__(self, protocol: str, name: str):
        self.protocol, self.name, self.seconds = protocol, name, 0.0
        self._ann = jax.profiler.TraceAnnotation(f"experiment.{name}")

    def __enter__(self) -> "_span":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        st = _stats(self.protocol)
        st[f"{self.name}_s"] += self.seconds
        st[f"{self.name}_n"] += 1


@dataclass(frozen=True)
class SweepSpec:
    """A sweep grid: cartesian product of rates (tx/s), PRNG seeds,
    network-scenario variants, and traffic-shape variants. Each entry of
    ``scenarios`` is a ``repro.scenarios.Scenario`` (None = fault-free
    baseline); each entry of ``workloads`` is a ``repro.workloads.Workload``
    (None = the §5.2 open-loop Poisson baseline). ``points()`` yields the
    flattened grid in rate-major order as (rate, seed, scenario_index,
    workload_index) — the same order ``run_sweep`` returns results in."""
    rates: Tuple[float, ...]
    seeds: Tuple[int, ...] = (0,)
    scenarios: Tuple = (None,)
    workloads: Tuple = (None,)

    def points(self) -> Iterator[Tuple[float, int, int, int]]:
        for rate, seed, fi, wi in itertools.product(
                self.rates, self.seeds, range(len(self.scenarios)),
                range(len(self.workloads))):
            yield float(rate), int(seed), fi, wi

    @property
    def size(self) -> int:
        return (len(self.rates) * len(self.seeds) * len(self.scenarios)
                * len(self.workloads))


def _count_build(protocol: str, mode: wlc.WorkloadMode) -> None:
    """Count one program build (a trace, or a load from the program
    store) in ``trace_counts()``, and in ``timing_stats()`` the arrival
    path the program takes: ``arrivals_hoisted`` (drawn before the tick
    scan) or ``arrivals_in_scan`` (drawn tick by tick, closed loop)."""
    _TRACE_COUNTS[protocol] = _TRACE_COUNTS.get(protocol, 0) + 1
    path = ("arrivals_hoisted" if harness.hoists_arrivals(mode)
            else "arrivals_in_scan")
    _stats(protocol)[path] += 1


def _sweep_body(protocol: str, cfg: SMRConfig, mode: wlc.WorkloadMode,
                env_b: Dict, wl_b: Dict, rate_b: jax.Array,
                seed_b: jax.Array) -> Dict:
    # body executes only while tracing, so this counts program builds
    _count_build(protocol, mode)
    return jax.vmap(lambda env, wlt, rate, seed: harness.sim_point(
        protocol, cfg, env, rate, seed, wlt, mode))(
        env_b, wl_b, rate_b, seed_b)


_sweep_compiled = partial(
    jax.jit, static_argnames=("protocol", "cfg", "mode"))(_sweep_body)

# materialized canonical programs by key: in-memory second level of the
# program store (the disk level lives in compile_cache.program_dir())
_PROGRAMS: Dict[str, "jax.stages.Wrapped"] = {}


def _program_key(protocol: str, cfg: SMRConfig, mode: wlc.WorkloadMode,
                 platform: str, args: tuple) -> str:
    """Disk key of one canonical program: everything that shapes the
    traced computation (protocol + cfg + workload-mode statics, the
    target platform, the arg pytree structure with shapes/dtypes) plus
    the source fingerprint — editing any simulator source invalidates
    every stored program."""
    import hashlib
    leaves, treedef = jax.tree.flatten(args)
    parts = [protocol, repr(cfg), repr(mode), platform,
             compile_cache.source_fingerprint(), str(treedef)]
    parts += [f"{np.asarray(x).dtype}{np.asarray(x).shape}" for x in leaves]
    return hashlib.sha256("|".join(parts).encode()).hexdigest()[:32]


def _acquire_program(protocol: str, cfg: SMRConfig, mode: wlc.WorkloadMode,
                     args: tuple):
    """Return the callable for the canonical sweep program, building it at
    most once ever: in-memory first, then the on-disk program store (a
    ``jax.export`` blob — loading skips tracing AND lowering), and only
    as a last resort a fresh trace (which is then serialized for every
    future process). The XLA executable underneath is covered separately
    by the persistent compilation cache. The program is exported for the
    platform the dispatch runs on (``ring_ops.target_platform``), so a
    process that runs on the TPU and on its host CPU keeps one of each."""
    platform = ring_ops.target_platform()
    key = _program_key(protocol, cfg, mode, platform, args)
    fn = _PROGRAMS.get(key)
    if fn is not None:
        return fn
    from jax import export as jax_export
    d = compile_cache.program_dir()
    path = d / f"{protocol}-{key}.bin" if d is not None else None
    if path is not None and path.exists():
        exp = jax_export.deserialize(path.read_bytes())
        # a loaded program counts as materialized, exactly like a fresh
        # trace would — per-process accounting stays identical whether
        # the store was warm or cold
        _count_build(protocol, mode)
    else:
        f = jax.jit(partial(_sweep_body, protocol, cfg, mode))
        # traces once (the body counts it)
        exp = jax_export.export(f, platforms=(platform,))(*args)
        if path is not None:
            path.write_bytes(exp.serialize())
    fn = jax.jit(exp.call)
    _PROGRAMS[key] = fn
    return fn


# mesh-sharded sweep programs, memoized per (protocol, statics, mesh):
# shard_map closures are fresh objects per call, so without this cache
# every dispatch would re-trace
_SHARDED: Dict[tuple, "jax.stages.Wrapped"] = {}
_SHARD_SIGNATURES: Dict[str, set] = {}


def shard_signatures() -> Dict[str, tuple]:
    """Distinct (ProgramSignature, devices) pairs dispatched through the
    sharded path per protocol since the last ``reset_trace_counts()``."""
    return {p: tuple(sorted(s)) for p, s in _SHARD_SIGNATURES.items()}


def _acquire_sharded(protocol: str, cfg: SMRConfig, mode: wlc.WorkloadMode,
                     mesh: "jax.sharding.Mesh"):
    """The mesh-sharded sweep program: the padded grid's leading axis is
    sharded over the 1-D ``("grid",)`` mesh and each device runs a
    ``jax.lax.map`` of the SAME single-lane point computation the
    canonical per-point path vmaps (``harness.sim_point`` with
    ``reduced=True``) — so per-point results are bitwise identical to the
    legacy dispatch loop while metrics reduce to O(sketch) bytes per
    point ON DEVICE before any host transfer. Tracing is counted in
    ``_TRACE_COUNTS`` like every other sweep program (the body runs only
    at trace time)."""
    key = (protocol, repr(cfg), repr(mode),
           tuple(d.id for d in mesh.devices.flat))
    fn = _SHARDED.get(key)
    if fn is not None:
        return fn
    from jax.sharding import PartitionSpec

    def body(env_b, wl_b, rate_b, seed_b):
        _count_build(protocol, mode)

        def one(point):
            env, wlt, rate, seed = point
            # one canonical lane per point: lift to the [1]-wide batch the
            # canonical program uses, then strip the lane axis
            out = jax.vmap(lambda e, w, r, s: harness.sim_point(
                protocol, cfg, e, r, s, w, mode, reduced=True))(
                jax.tree.map(lambda x: x[None], env),
                jax.tree.map(lambda x: x[None], wlt),
                rate[None], seed[None])
            return jax.tree.map(lambda x: x[0], out)

        return jax.lax.map(one, (env_b, wl_b, rate_b, seed_b))

    spec = PartitionSpec(dmesh.GRID_AXIS)
    fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=spec,
                               out_specs=spec, check_vma=False))
    _SHARDED[key] = fn
    return fn


def _lower(cfg: SMRConfig, spec: SweepSpec, canonical: bool = True):
    """Flatten the grid to stacked per-point inputs (env leaves, workload
    table leaves, rate, seed) plus the static workload mode and the
    horizon-resolved cfg (one ring shape for the whole grid). With
    ``canonical`` (the default), the shape axes are rounded to the
    canonical program signature: window tables pad to a power-of-two
    floor (pad rows are never indexed — ``win_of_tick`` only addresses
    real windows), the auto horizon rounds up to
    ``netsim.CANONICAL_HORIZON``, and the signature's width is
    ``CANONICAL_LANES`` — ``dispatch_sweep`` then runs the grid in chunks
    of ``_lane_chunks`` widths (lanes are independent under vmap, so
    chunked execution is bitwise identical to one wide dispatch; pinned
    in tests)."""
    from repro import scenarios as sc
    pts = list(spec.points())
    # lower every scenario ONCE: the tables feed both the sweep-wide
    # horizon resolution and the padded env stack. build_env gets the
    # ORIGINAL cfg (envs don't embed the horizon), so its static-delay
    # validation sees the user's auto-vs-pinned intent exactly as a
    # direct build_env call would; only the compiled program takes the
    # sweep-wide resolved horizon.
    stabs = [sc.lower(cfg, sc.as_scenario(f)) for f in spec.scenarios]
    n_windows = max(t["alive"].shape[0] for t in stabs)
    wl_pad = max(wlc.compile.n_windows(cfg, w) for w in spec.workloads)
    lanes = len(pts)
    if canonical:
        n_windows = _canon_pow2(n_windows, CANONICAL_MIN_WINDOWS)
        wl_pad = _canon_pow2(wl_pad, CANONICAL_MIN_WINDOWS)
        lanes = CANONICAL_LANES
    # stack host-side (numpy), not netsim.stack_envs (device): the lane
    # gather below and the per-chunk slices in dispatch_sweep then cost
    # nothing instead of compiling one gather program per leaf shape
    stack = jax.tree.map(
        lambda *xs: np.stack([np.asarray(x) for x in xs]),
        *[netsim.build_env(cfg, f, n_windows, tab=t)
          for f, t in zip(spec.scenarios, stabs)])
    cfg = netsim.resolve_horizon(cfg, tabs=stabs, canonical=canonical)
    # the stacks always hold every real point; ``lanes`` is the width of
    # the native program, or of a one-point canonical chunk
    # (dispatch_sweep chunks the grid)
    lane_pts = pts
    fidx = np.array([fi for _, _, fi, _ in lane_pts], np.int32)
    env_b = jax.tree.map(lambda x: x[fidx], stack)
    # the static workload mode is judged on the UNPADDED lowerings —
    # canonical window padding must not kick a trivial (all-ones
    # single-window) grid off the seed-identical fast path
    mode = wlc.mode_of([wlc.lower(cfg, w) for w in spec.workloads])
    tabs = [wlc.lower(cfg, w, pad_windows=wl_pad) for w in spec.workloads]
    widx = np.array([wi for _, _, _, wi in lane_pts], np.int32)
    # win_start is host-side metadata (ragged across workloads); only the
    # fixed-shape device tables ride into the compiled program. All lane
    # stacks stay host-side numpy so per-chunk slicing is free (device
    # slicing would compile one gather program per leaf shape)
    dev = [{k: v for k, v in t.items() if k != "win_start"} for t in tabs]
    wl_b = jax.tree.map(lambda *xs: np.stack(xs)[widx], *dev)
    # per-replica Poisson rate per tick, computed host-side in float64 so a
    # batched grid and a single run_sim see bit-identical inputs
    # lint: allow(dtype-hygiene): deliberate f64 host math for grid /
    # single-run bit-exactness; .astype(np.float32) before the device
    rate_b = (np.array([r for r, _, _, _ in lane_pts], np.float64)
              * cfg.tick_ms / 1000.0 / cfg.n_replicas).astype(np.float32)
    seed_b = np.array([s for _, s, _, _ in lane_pts], np.int32)
    sig = ProgramSignature(
        n=cfg.n_replicas, ticks=netsim.sim_ticks(cfg), lanes=lanes,
        scen_windows=n_windows, wl_windows=wl_pad,
        horizon=int(cfg.delay_horizon_ticks),
        trivial=mode.trivial, closed=mode.closed)
    return pts, cfg, mode, env_b, wl_b, rate_b, seed_b, sig


class PendingSweep:
    """A dispatched sweep whose device computation may still be running.
    ``collect()`` blocks on the results and materializes the per-point
    dicts. Dispatching several sweeps before collecting any (see
    ``run_sweeps``) overlaps each program's device execution with the
    next program's trace/lowering — on a warm persistent cache that
    overlap is most of a fig suite's wall-clock."""

    def __init__(self, protocol: str, *, results: List[Dict] = None,
                 pts=None, wl_names=None, outs=None, n_real=None):
        self.protocol = protocol
        self._results = results   # analytic protocols resolve eagerly
        self._pts = pts
        self._wl_names = wl_names
        self._outs = outs         # async device-array trees, one per chunk
        self._n_real = n_real     # sharded path: real points before padding

    def point_devices(self) -> List[int]:
        """Id of the device that holds each dispatched point's results,
        padding rows of a sharded grid included (read before
        ``collect()``)."""
        ids: List[int] = []
        for o in self._outs:
            leaf = jax.tree.leaves(o)[0]
            rows = [None] * leaf.shape[0]
            for sh in leaf.addressable_shards:
                for r in range(leaf.shape[0])[sh.index[0]]:
                    rows[r] = sh.device.id
            ids += rows
        return ids

    def collect(self) -> List[Dict]:
        if self._results is not None:
            return self._results
        with _span(self.protocol, "readback") as sp:
            chunks = [jax.tree.map(np.asarray, o) for o in self._outs]
            # tree-aware concat: with tracing on, sim_point outputs carry
            # nested subtrees (per-layer obs rings), not just flat arrays
            out = (chunks[0] if len(chunks) == 1 else
                   jax.tree.map(lambda *xs: np.concatenate(xs, axis=0),
                                *chunks))
            if self._n_real is not None:
                # sharded decode: drop the rows that padded the grid to a
                # multiple of the mesh size (repeats of the last real point)
                out = jax.tree.map(lambda x: x[:self._n_real], out)
        _stats(self.protocol)["run_s"] += sp.seconds
        self._outs = None
        with _span(self.protocol, "decode"):
            self._results = self._rows(out)
        return self._results

    def _rows(self, out: Dict) -> List[Dict]:
        """One result dict per point from the host copies of the outputs."""
        results: List[Dict] = []
        for i, (rate, seed, fi, wi) in enumerate(self._pts):
            r: Dict = {"protocol": self.protocol, "rate": rate,
                       "seed": seed,
                       "workload": self._wl_names[wi],
                       "throughput": float(out["throughput"][i]),
                       "median_ms": float(out["median_ms"][i]),
                       "p99_ms": float(out["p99_ms"][i]),
                       "committed": float(out["committed"][i])}
            # per-batch/per-tick arrays: present on the legacy path,
            # replaced by the fixed-size sketch on the reduced path
            for k in ("timeline", "origin_median_ms", "origin_p99_ms",
                      "origin_timeline", "origin_lat_ms_timeline"):
                if k in out:
                    r[k] = out[k][i]
            if self.protocol == "mandator-sporades":
                r["async_frac"] = float(out["async_frac"][i])
                r["views"] = int(out["views"][i])
                if "cvc_all" in out:
                    r["cvc_all"] = out["cvc_all"][i]
                if "commit_key" in out:
                    r["commit_key"] = out["commit_key"][i]
            if "inflight_max" in out:
                r["inflight_max"] = out["inflight_max"][i]
            # flight-recorder outputs (absent at TraceLevel.OFF, so the
            # default result schema is untouched)
            for k in ("phase_med_ms", "phase_p99_ms", "phase_origin_med_ms",
                      "phase_origin_p99_ms", "batch_marks_t", "batch_arr_t",
                      "batch_n"):
                if k in out:
                    r[k] = out[k][i]
            if "sketch" in out:
                r["sketch"] = {"v": out["sketch"]["v"][i],
                               "w": out["sketch"]["w"][i]}
            if "obs" in out:
                r["obs"] = jax.tree.map(lambda x: x[i], out["obs"])
            # health-monitor outputs (absent at MonitorLevel.OFF)
            if "mon" in out:
                r["mon"] = jax.tree.map(lambda x: x[i], out["mon"])
            results.append(r)
        return results


def dispatch_sweep(protocol: str, cfg: SMRConfig, spec: SweepSpec,
                   canonical: bool = True, mesh=None) -> PendingSweep:
    """Lower + dispatch the grid without blocking on the device
    computation. ``canonical`` pads the program to the canonical
    signature (see ``_lower``) so shape-compatible sweeps share one
    compiled program. Analytic baselines (host loops) resolve eagerly.

    ``mesh`` selects the mesh-sharded engine: None (default) keeps the
    chunked dispatch loop (one launch a ``_lane_chunks`` chunk); an int
    or a ``jax.sharding.Mesh`` with a ``("grid",)`` axis (see
    ``repro.distributed.mesh``) shards the flattened grid's leading axis
    over the mesh devices as ONE dispatch,
    each device scanning its grid slice with the same canonical
    single-lane point program and reducing metrics on device to a
    fixed-size latency sketch (``harness.sim_point(reduced=True)``).
    Analytic protocols ignore ``mesh`` (host loops have no device
    program)."""
    wl_names = [wlc.as_workload(w).name for w in spec.workloads]
    if protocol in ANALYTIC_PROTOCOLS:
        if protocol == "epaxos":
            from repro.core.epaxos import run_epaxos_model as model
        else:
            from repro.core.rabia import run_rabia_model as model
        out = []
        for rate, seed, fi, wi in spec.points():
            r = model(cfg, rate, spec.scenarios[fi],
                      workload=spec.workloads[wi])
            r["seed"] = seed
            r["workload"] = wl_names[wi]
            out.append(r)
        return PendingSweep(protocol, results=out)
    if protocol not in harness.SCAN_PROTOCOLS:
        raise ValueError(protocol)

    compile_cache.ensure()
    mesh = dmesh.as_grid_mesh(mesh)
    with _span(protocol, "lower"):
        pts, cfg, mode, env_b, wl_b, rate_b, seed_b, sig = _lower(
            cfg, spec, canonical=canonical)
        pad = (-len(pts)) % int(mesh.devices.size) if mesh is not None else 0
        if pad:
            # pad the grid to a multiple of the mesh size by repeating the
            # last real point; collect() slices the repeats back off
            idx = np.concatenate([np.arange(len(pts)),
                                  np.full(pad, len(pts) - 1)]).astype(np.int64)
            env_b = jax.tree.map(lambda x: x[idx], env_b)
            wl_b = jax.tree.map(lambda x: x[idx], wl_b)
            rate_b, seed_b = rate_b[idx], seed_b[idx]
    if mesh is not None:
        # the sharded path registers the SAME canonical signature — the
        # point computation (and so the persistent-cache key material) is
        # unchanged; only the orchestration around it is
        _SIGNATURES.setdefault(protocol, set()).add(sig)
        _SHARD_SIGNATURES.setdefault(protocol, set()).add(
            (sig, int(mesh.devices.size)))
        widths = []
    else:
        # canonical: chunks of ``_lane_chunks`` widths, launched async in
        # grid order (lanes are independent under vmap, so this is
        # bitwise identical to one wide dispatch); native: one launch
        widths = (_lane_chunks(len(pts), ring_ops.target_platform(),
                               _lane_ring_bytes(protocol, cfg))
                  if canonical else [sig.lanes])
        _SIGNATURES.setdefault(protocol, set()).update(
            replace(sig, lanes=w) for w in widths)
    traces_before = _TRACE_COUNTS.get(protocol, 0)
    with _span(protocol, "enqueue") as sp:
        if mesh is not None:
            fn = _acquire_sharded(protocol, cfg, mode, mesh)
            outs = [fn(env_b, wl_b, rate_b, seed_b)]
        else:
            outs, progs, lo = [], {}, 0
            for w in widths:
                # host-side numpy slices: free
                chunk = jax.tree.map(lambda x: x[lo:lo + w],
                                     (env_b, wl_b, rate_b, seed_b))
                lo += w
                if w not in progs:
                    # canonical programs additionally go through the
                    # on-disk program store: warm processes deserialize
                    # the traced computation instead of re-tracing it (the
                    # persistent XLA cache below then supplies the
                    # executable); each width is a program of its own
                    progs[w] = (
                        _acquire_program(protocol, cfg, mode, chunk)
                        if canonical
                        else partial(_sweep_compiled, protocol, cfg, mode))
                outs.append(progs[w](*chunk))
    stats = _stats(protocol)
    # dispatch returns before the device finishes: an enqueue that traced
    # is pure trace + lower + (backend compile | cache load); collect()
    # adds the execution + readback wall to run_s
    traced = _TRACE_COUNTS.get(protocol, 0) > traces_before
    stats["compile_s" if traced else "run_s"] += sp.seconds
    stats["dispatches"] += 1
    stats["horizon"] = int(cfg.delay_horizon_ticks)
    shape = f"n{cfg.n_replicas}.d{stats['horizon']}"
    stats["by_shape"][shape] = stats["by_shape"].get(shape, 0) + 1
    for w in widths:
        stats["by_lanes"][str(w)] = stats["by_lanes"].get(str(w), 0) + 1
    return PendingSweep(protocol, pts=pts, wl_names=wl_names, outs=outs,
                        n_real=len(pts) if mesh is not None else None)


def run_sweep(protocol: str, cfg: SMRConfig, spec: SweepSpec,
              canonical: bool = True, mesh=None) -> List[Dict]:
    """Run the whole grid; returns one result dict per point, in
    ``spec.points()`` order. Scan protocols execute as a single vmapped
    device dispatch; analytic baselines loop on the host. ``mesh``
    selects the mesh-sharded engine (see ``dispatch_sweep``)."""
    return dispatch_sweep(protocol, cfg, spec, canonical=canonical,
                          mesh=mesh).collect()


def run_sweeps(requests) -> List[List[Dict]]:
    """Dispatch every (protocol, cfg, spec) request before collecting any,
    so device execution overlaps host-side tracing/lowering of the later
    programs. Returns per-request result lists in request order —
    identical to ``[run_sweep(*r) for r in requests]``, just faster."""
    pending = [dispatch_sweep(p, cfg, spec) for p, cfg, spec in requests]
    return [p.collect() for p in pending]
