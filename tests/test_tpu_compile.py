"""Compile the main path for a described TPU v5e chip, with no chip.

The TPU compiler is installed next to the CPU backend, and compiles for a
topology that is described and not attached. It refuses what the Pallas
interpreter accepts (scatters and unaligned lane slices in a kernel body,
VMEM overruns), so these tests pin that the ring-commit kernel and the
whole canonical sweep programs lower to a ``tpu_custom_call`` for v5e.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every pytest worker
imports this file.
"""
import contextlib
import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import workloads as wl
from repro.configs.smr import SMRConfig
from repro.core import channel as ch
from repro.core import experiment, mandator, paxos, sporades
from repro.core.experiment import SweepSpec

pytestmark = pytest.mark.no_persistent_cache


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(x, sharding):
    return jax.ShapeDtypeStruct(jnp.shape(x), jnp.asarray(x).dtype,
                                sharding=sharding)


def _compile_commit(spec: ch.RingSpec, d: int, n: int, sharding,
                    names) -> str:
    """Compile one tick's ring commit of ``names`` sends (a channel named
    twice is sent twice) with the compiled Pallas kernel; returns the
    optimized HLO text."""
    widths = {c.name: c.width for c in spec.channels}

    def commit(buf, t, pays, delays, masks, drop):
        sends = [ch.Send(nm, p, dl, m)
                 for nm, p, dl, m in zip(names, pays, delays, masks)]
        return ch.ring_commit(spec, {"buf": buf}, t, sends, drop=drop,
                              backend="pallas")["buf"]

    f32, i32, b = jnp.float32, jnp.int32, jnp.bool_
    sds = partial(jax.ShapeDtypeStruct, sharding=sharding)
    args = (sds((d, n, n, spec.k), f32), sds((), i32),
            [sds((n, n, widths[nm]), f32) for nm in names],
            [sds((n, n), i32) for _ in names],
            [sds((n, n), b) for _ in names], sds((n, n), b))
    return jax.jit(commit).lower(*args).compile().as_text()


@pytest.mark.parametrize("d", [256, 1024])
@pytest.mark.parametrize("n", [5, 9])
def test_ring_commit_kernel_compiles_sporades_ring(one_chip, n, d):
    """The Sporades ring at five replicas (K = 50, rows padded 5 -> 8) and
    at fig 9's nine (K = 78, rows padded 9 -> 16), at the fig6 (256-slot)
    and paper-ddos (1,024-slot) horizons, with one channel sent twice so
    the kernel merges two max planes."""
    spec = sporades.ring_spec(n)
    assert spec.k == {5: 50, 9: 78}[n]
    names = [c.name for c in spec.channels] + ["vote"]
    hlo = _compile_commit(spec, d, n, one_chip, names)
    assert "tpu_custom_call" in hlo


def test_ring_commit_kernel_compiles_multipaxos_ring(one_chip):
    """The Multi-Paxos ring, whose ``fw`` channel add-merges."""
    n = 5
    spec = paxos.ring_spec(n, False)
    assert any(c.additive for c in spec.channels)
    names = [c.name for c in spec.channels]
    hlo = _compile_commit(spec, 256, n, one_chip, names)
    assert "tpu_custom_call" in hlo


def _compile_canonical(sharding, protocol: str = "mandator-sporades",
                       n: int = 5, lanes: int = 1, horizon: int = 256,
                       closed_loop: bool = False):
    """One whole canonical sweep program of ``protocol`` at ``n``
    replicas (``lanes`` wide, rings of ``horizon`` slots: 256 is the
    canonical ring; open-loop Poisson clients, or ``ClosedLoop`` pools)
    with the Pallas commit, compiled for ``sharding``'s device."""
    cfg = SMRConfig(n_replicas=n, sim_seconds=1.0, channel_backend="pallas",
                    delay_horizon_ticks=horizon)
    traffic = {"workloads": (wl.Workload("closed-loop", (
        wl.ClosedLoop(),)),)} if closed_loop else {}
    _, cfg, mode, env_b, wl_b, rate_b, seed_b, sig = experiment._lower(
        cfg, SweepSpec(rates=(150_000,), seeds=tuple(range(lanes)),
                       **traffic))
    assert sig.lanes == 1 and sig.horizon == horizon
    assert mode.closed == closed_loop
    args = jax.tree.map(lambda x: _shape(x[:lanes], sharding),
                        (env_b, wl_b, rate_b, seed_b))
    fn = jax.jit(partial(experiment._sweep_body, protocol, cfg, mode))
    return fn.lower(*args).compile()


def _rings_in_vmem(hlo: str, specs, n: int, lanes: int, horizon: int):
    """Each packed ring's tiled buffers in ``hlo`` sit in VMEM (memory
    space ``S(1)``)."""
    for spec in specs:
        layouts = set(re.findall(
            rf"f32\[{lanes},{horizon},{n},{n},{spec.k}\]\{{([^}}]*)\}}",
            hlo))
        tiled = [lay for lay in layouts if "T(8,128)" in lay]
        assert tiled and all("S(1)" in lay for lay in tiled), (spec.k,
                                                                layouts)


@pytest.fixture(scope="module")
def canonical_ms(one_chip):
    """The canonical program of ``n`` replicas, compiled once a module."""
    compiled = {}

    def get(n: int = 5):
        if n not in compiled:
            compiled[n] = _compile_canonical(one_chip, n=n)
        return compiled[n]
    return get


@pytest.mark.parametrize("n", [5, 9])
def test_canonical_sporades_program_compiles(canonical_ms, n):
    """The canonical mandator-sporades program, at five replicas and at
    fig 9's nine, compiles for one v5e chip with the Pallas commit in
    it."""
    compiled = canonical_ms(n)
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("horizon", [256, 1024])
def test_grid_chunk_program_keeps_its_rings_in_vmem(one_chip, horizon):
    """The multi-lane program a 16-point grid runs as on the TPU (8 lanes
    at fig 6's 256 slots, 2 at the DDoS horizon's 1,024) compiles for one
    v5e chip with the Pallas commit batched over its lanes, in well under
    the chip's memory, and the compiler keeps both packed rings in VMEM
    (memory space ``S(1)``) across the scan: the width rule's premise."""
    cfg = SMRConfig(sim_seconds=1.0, delay_horizon_ticks=horizon)
    (lanes,) = set(experiment._lane_chunks(
        16, "tpu", experiment._lane_ring_bytes("mandator-sporades", cfg)))
    assert lanes > 1
    compiled = _compile_canonical(one_chip, lanes=lanes, horizon=horizon)
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
    _rings_in_vmem(hlo, (mandator.ring_spec(), sporades.ring_spec(5)), 5,
                   lanes, horizon)


@pytest.mark.parametrize("n", [5, 9])
def test_canonical_multipaxos_program_compiles(one_chip, n):
    """The whole canonical Multi-Paxos program, at five replicas and at
    nine, compiles for one v5e chip with the Pallas commit in it, and the
    compiler keeps its one packed ring (with the add-merged ``fw``
    channel) in VMEM at the canonical 256 slots."""
    compiled = _compile_canonical(one_chip, "multipaxos", n=n)
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
    _rings_in_vmem(hlo, (paxos.ring_spec(n, False),), n, 1, 256)


def test_closed_loop_sporades_program_compiles(one_chip):
    """The closed-loop Mandator-Sporades program (``ClosedLoop`` clients:
    the in-flight count rides the scan carry and falls as batches commit)
    compiles for one v5e chip with the Pallas commit in it."""
    compiled = _compile_canonical(one_chip, closed_loop=True)
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


LAYER_SCOPES = ("arrivals", "ring_deliver", "ring_commit", "mandator",
                "sporades", "extract")


def _op_names(hlo: str):
    return re.findall(r'op_name="([^"]*)"', hlo)


@pytest.mark.parametrize("n", [5, 9])
def test_named_scopes_reach_the_chip_program(canonical_ms, n):
    """Every layer's named scope is in the compiled program's ``op_name``
    metadata, and the Pallas commit lies under ``ring_commit``."""
    hlo = canonical_ms(n).as_text()
    comps = {c for name in _op_names(hlo) for c in name.split("/")}
    for scope in LAYER_SCOPES:
        assert scope in comps or f"vmap({scope})" in comps, scope
    kernels = [ln for ln in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    assert kernels
    for ln in kernels:
        (name,) = _op_names(ln)
        assert "/ring_commit/" in name, name


def test_named_scopes_add_no_instruction(canonical_ms, one_chip,
                                         monkeypatch):
    """The scopes are metadata only: with ``jax.named_scope`` a null
    context the chip's compiler emits the same instructions, in the same
    order, on the same shapes. Only the numbers in instruction names may
    differ (the lowering numbers ops as it emits them)."""
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _compile_canonical(one_chip).as_text()
    assert "/ring_commit/" not in bare

    def instructions(hlo):
        return [re.sub(r"%[\w.\-]+", "%",
                       re.sub(r",? metadata=\{[^}]*\}", "", ln))
                for ln in hlo.splitlines() if " = " in ln]
    scoped = instructions(canonical_ms().as_text())
    assert len(scoped) > 1000
    assert scoped == instructions(bare)
